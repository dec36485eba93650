"""A secp256k1 configuration's data, made from the seed by the plain
reference (benchmarks/reference_secp.py): the validator set in the
program's types with the reference's keys in set order.  Commits over
it are benchmarks/data.make_commits', which signs the reference's
sign-bytes with whatever key the set holds."""

from __future__ import annotations

from . import data, reference_secp


def make_valset(config: dict, seed: int) -> data.Valset:
    from cometbft_tpu.crypto import secp256k1 as program_secp
    from cometbft_tpu.types.validators import Validator, ValidatorSet

    if config["key_type"] != "secp256k1":
        raise ValueError(f"no reference for key type {config['key_type']!r}")
    keys = [
        reference_secp.SigningKey(seed, config["name"].encode(), i)
        for i in range(config["validators"])
    ]
    by_pub = {k.public: k for k in keys}
    vals = ValidatorSet([
        Validator(program_secp.PubKey(pub), config["assumed"]["voting_power"])
        for pub in by_pub
    ])
    return data.Valset(
        chain_id=config["assumed"]["chain_id"],
        vals=vals,
        keys=[by_pub[v.pub_key.bytes()] for v in vals.validators],
        seed=seed,
        t_genesis=1_700_000_000 + seed % 1000,
    )
