"""Flagship verification-plane pipelines.

The "model" of this framework is the commit-verification pipeline: batched
Ed25519 signature verification plus Merkle tree hashing compiled as fused
XLA programs, optionally sharded over a device mesh (cometbft_tpu.parallel).
benchmarks/run.py, chip_smoke.py and __graft_entry__.py drive these.
"""
