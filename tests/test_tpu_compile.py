"""The on-chip kernels of the main path, compiled at the cells' widths
for a TPU v5e that is described and not attached: what interpret mode
cannot show (a block shape Mosaic refuses, an operation it cannot lower)
fails here, on the CPU, at no chip time.  Nothing runs, so this says
nothing of results or speed: chip_smoke.py compares the lowered kernel
with the array form on the chip.

Every such compile lives in THIS file and behind the fixture below: one
process at a time may load the TPU's library, and only a test that has
started may ask for it (never an import, a skipif or a parametrize).
"""

import re

import pytest

import jax
import jax.numpy as jnp

from cometbft_tpu.ops import field as F


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "chain", [F._pow_p58_chain, F._invert_chain], ids=["pow_p58", "invert"]
)
@pytest.mark.parametrize("lanes", [256, 10_112])
def test_exponentiation_kernel_compiles_for_the_chip(one_chip, chain, lanes):
    """The cells' lane counts: one (22, 2, 128) tile, and ten tiles of
    eight rows with a padded one.  The custom call keeps the scope it
    was traced under, which is how a profile's reader finds it."""

    def program(x):
        with jax.named_scope("decompress"):
            return F._on_chip(chain, x)

    x = jax.ShapeDtypeStruct((F.NLIMBS, lanes), jnp.int32, sharding=one_chip)
    hlo = jax.jit(program).lower(x).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    rows, _ = F.tile_rule(lanes)
    assert f"s32[{F.NLIMBS},{rows},{F.LANES}]" in calls[0]
    (op_name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "decompress" in op_name.split("/")


@pytest.mark.parametrize("lanes", [256, 10_112])
def test_chain_kernel_compiles_for_the_chip(one_chip, lanes):
    """The comb's chains and their fold at the cells' lane counts (K = 4
    chains in one (8, 128) tile; K = 1 in ten tiles of eight rows, the
    last one ragged): one custom call, under the scope the benchmark's
    reader counts as tree_reduce."""
    from cometbft_tpu.ops import comb, ed25519 as E

    k = F.chain_rule(lanes)
    steps = -(-comb.NPART // k)

    def program(partials, nr):
        with jax.named_scope("tree_reduce"):
            return comb._chains_on_chip([E.Niels(*partials)], E.Point(*nr), k)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    hlo = jax.jit(program).lower(
        tuple(arg(steps * k, F.NLIMBS, lanes) for _ in range(3)),
        tuple(arg(F.NLIMBS, lanes) for _ in range(4)),
    ).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    rows = k * -(-lanes // F.LANES)  # 8 at 256 lanes, 79 at 10,112
    assert f"s32[3,{F.NLIMBS},{steps},{rows},{F.LANES}]" in calls[0]
    (op_name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "tree_reduce" in op_name.split("/")
