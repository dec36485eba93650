"""The light client against the plain reference of light verification
(benchmarks/reference_light.py), over chains whose validator set moves
by one key a block (benchmarks/light_chain.py): every hop the client
tries and its result, what it fetches, what it ends up trusting and
how a walk ends, for the honest chain, flipped signatures, a wrong
``validators_hash``, an expired trusted header and a gap no bisection
can cross.  At 12 validators the batches take the host route; at 40 (over
``comb_min()``) they run the comb program at 128 lanes, bound by address
to the trusted set and by index to the new one.  The light sign-bytes are
the protocol's (111 bytes), so this is the fast tier's second comb shape,
(128, 196): the first, (128, 100), holds messages of 32 bytes.
"""

import os
import sys
from fractions import Fraction

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import light_chain, reference  # noqa: E402
from benchmarks import reference_light as ref  # noqa: E402
from benchmarks.drivers.light_walk import (  # noqa: E402
    flipped_rows, hop_spans, trusted_heights,
)
from cometbft_tpu.light import (  # noqa: E402
    Client, ErrInvalidHeader, ErrNewValSetCantBeTrusted, ErrOldHeaderExpired,
    LightStore, TrustOptions,
)
from cometbft_tpu.store.db import MemDB  # noqa: E402
from cometbft_tpu.types.validation import CommitVerificationError  # noqa: E402
from cometbft_tpu.utils import tracing  # noqa: E402
from cometbft_tpu.utils.metrics import hub  # noqa: E402

HEIGHTS = 64
PERIOD_NS = 24 * 3600 * ref.NS
LEVEL = Fraction(1, 3)
CONFIG = {
    "name": "light-test", "heights": HEIGHTS, "validators": 12,
    "assumed": {"chain_id": "light-ref-test", "voting_power": 10,
                "block_seconds": 60},
}
_CHAINS: dict[int, light_chain.Chain] = {}


def chain_of(width: int) -> light_chain.Chain:
    if width not in _CHAINS:
        _CHAINS[width] = light_chain.Chain(CONFIG, 77 + width, validators=width)
    return _CHAINS[width]


def now_of(chain) -> int:
    return (chain.seconds(HEIGHTS) + 60) * ref.NS


@pytest.fixture
def ring():
    was_on = tracing.enabled()
    tracing.set_enabled(True, ring_capacity=1 << 16)
    tracing.reset()
    yield
    tracing.set_enabled(was_on)
    tracing.reset()


def client_walk(chain, provider, now_ns):
    """(how it ended, what was raised, heights trusted)."""
    db = MemDB()
    ended, err = ref.OK, None
    try:
        client = Client(
            chain.chain_id,
            TrustOptions(PERIOD_NS, 1, chain.block(1).header.hash()),
            provider, [provider], LightStore(db), trust_level=LEVEL,
        )
        client.verify_light_block_at_height(HEIGHTS, now_ns)
    except ErrNewValSetCantBeTrusted as e:
        ended, err = ref.CANT_BE_TRUSTED, e
    except (ErrInvalidHeader, ErrOldHeaderExpired, CommitVerificationError) as e:
        ended, err = ref.REFUSED, e
    return ended, err, trusted_heights(db)


def first_hop(chain):
    """The first accepted hop of the honest walk that is not adjacent."""
    want = ref.walk(chain.block, 1, HEIGHTS, now_of(chain), PERIOD_NS, LEVEL)
    return next((a, b) for a, b, r in want.hops if r.kind == ref.OK and b > a + 1)


def case(chain, name):
    """(provider arguments, now) of a named case."""
    now = now_of(chain)
    if name == "honest":
        return {}, now
    a, b = first_hop(chain)
    if name == "flipped signatures":
        idxs = sorted(flipped_rows(ref.commit_rows(chain.block(b))[0]))
        return {"replaced": {b: chain.flipped(b, idxs)}}, now
    if name == "wrong validators_hash":
        return {"replaced": {b: chain.wrong_set_hash(b)}}, now
    if name == "expired trusted header":
        return {}, chain.seconds(1) * ref.NS + PERIOD_NS
    if name == "a gap no bisection can cross":
        return {"missing": range(2, HEIGHTS)}, now
    raise KeyError(name)


CASES = ["honest", "flipped signatures", "wrong validators_hash",
         "expired trusted header", "a gap no bisection can cross"]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("width", [12, 40])
def test_client_walks_as_the_reference_does(ring, monkeypatch, width, name):
    if width >= 32:
        # 14 to 27 live rows a pass: under the device floor, which is a
        # production threshold and no part of what is compared here
        monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    chain = chain_of(width)
    kwargs, now = case(chain, name)
    want = ref.walk(light_chain.Provider(chain, **kwargs).block_at, 1, HEIGHTS,
                    now, PERIOD_NS, LEVEL)
    counted = hub().light_hops.expose()
    provider = light_chain.Provider(chain, **kwargs)
    ended, err, trusted = client_walk(chain, provider, now)
    assert hop_spans() == [(a, b, r.kind) for a, b, r in want.hops]
    assert provider.fetched == want.fetched
    assert (ended, trusted) == (want.ended, want.trusted)
    assert want.ended == (ref.OK if name == "honest" else
                          ref.CANT_BE_TRUSTED if "gap" in name else ref.REFUSED)
    last = want.hops[-1][2]
    if last.index is not None:
        assert f"(#{last.index})" in str(err)
    if name == "wrong validators_hash":
        assert last.reason.startswith("validators_hash") and "validators hash" in str(err)
    if name == "expired trusted header":
        assert isinstance(err, ErrOldHeaderExpired) and last.reason.endswith("expired")
    assert hub().light_hops.expose() != counted  # the counter moved too


@pytest.mark.parametrize("width", [12, 40])
def test_set_moves_by_one_key_a_block(width):
    chain = chain_of(width)
    for h, d in ((1, 1), (5, 7), (3, width)):
        a = {v.pub for v in chain.vals(h)}
        b = {v.pub for v in chain.vals(h + d)}
        assert len(a) == len(b) == width and len(a & b) == width - d
    assert [v.address for v in chain.vals(9)] == sorted(
        v.address for v in chain.vals(9))
    assert chain.block(9) is chain.block(9)
    again = light_chain.Chain(CONFIG, 77 + width, validators=width)
    assert again.block(9).sigs == chain.block(9).sigs  # the seed alone


@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 8, 13, 175])
def test_merkle_root_equals_the_programs(n):
    from cometbft_tpu.crypto import merkle

    leaves = [bytes([i & 0xFF]) * (i % 7 + 1) for i in range(n)]
    assert ref.merkle_root(leaves) == merkle.hash_from_byte_slices(
        leaves, device=False)


@pytest.mark.parametrize("h", [1, 2, 63, 64])
def test_header_set_and_sign_bytes_equal_the_programs(h):
    """The reference encodes header, validator set and vote by hand; the
    program's own types, decoded from the wire, must hash and sign to
    the same bytes."""
    chain = chain_of(12)
    block = chain.block(h)
    lb = chain.light_block(h)
    lb.validate_basic(chain.chain_id)
    assert lb.hash == block.header.hash()
    assert lb.validator_set.hash() == ref.valset_hash(block.vals)
    assert lb.signed_header.header.next_validators_hash == ref.valset_hash(
        chain.vals(h + 1))
    assert [v.address for v in lb.validator_set.validators] == [
        v.address for v in block.vals]
    program_bytes = lb.signed_header.commit.vote_sign_bytes_fn(chain.chain_id)
    for i in (0, 5, 11):
        assert program_bytes(i) == block.sign_bytes(i)
        assert reference.verify(block.vals[i].pub, block.sign_bytes(i),
                                block.sigs[i].signature)


def test_a_hop_names_the_first_bad_signature_it_counts():
    chain = chain_of(12)
    a, b = first_hop(chain)
    now = now_of(chain)
    t_rows, _ = ref.trusting_rows(chain.block(a), chain.block(b), LEVEL)
    c_rows, _ = ref.commit_rows(chain.block(b))
    counted = {i for i, _ in t_rows} | {i for i, _ in c_rows}
    uncounted = [i for i in range(12) if i not in counted]
    assert ref.hop(chain.block(a), chain.block(b), now, PERIOD_NS).kind == ref.OK
    late = max(i for i, _ in c_rows)
    res = ref.hop(chain.block(a), chain.flipped(b, [late, t_rows[0][0]]), now,
                  PERIOD_NS)
    assert (res.kind, res.index) == (ref.REFUSED, t_rows[0][0])
    res = ref.hop(chain.block(a), chain.flipped(b, [late]), now, PERIOD_NS)
    assert (res.kind, res.index) == (ref.REFUSED, late)
    if uncounted:  # a signature nobody counts is nobody's business
        assert ref.hop(chain.block(a), chain.flipped(b, uncounted[-1:]), now,
                       PERIOD_NS).kind == ref.OK
    far = ref.hop(chain.block(1), chain.block(HEIGHTS), now, PERIOD_NS)
    assert far.kind == ref.CANT_BE_TRUSTED
    adjacent = ref.hop(chain.block(1), chain.block(2), now, PERIOD_NS)
    assert adjacent.kind == ref.OK
