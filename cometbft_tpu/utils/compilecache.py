"""JAX's persistent compilation cache: one rule, in this one place.

Cold compiles are the large part of a cold start here (the fused
Ed25519 kernel is minutes on the CPU backend; the 10,000-validator comb
table build and verify programs are the large part of a first run on a
TPU), so every entry point — ``python -m cometbft_tpu``,
``benchmarks/run.py``, ``chip_smoke.py``, the test suite — calls
:func:`enable` before its first compile.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable by itself,
  and this module sets no directory at all — whoever launched the
  process placed the cache.
* unset: ``<checkout>/tests/.jax_cache``, computed from this module's
  own path.  The path is part of what makes a cache warm (tier-1 fits
  its time limit only because that directory is), so it is fixed: never
  a temporary directory, a pid or a time.

The key holds the program's metadata
(``jax_compilation_cache_include_metadata_in_key``): jax 0.9 strips
``jax.named_scope`` names and source locations before it hashes a
program, so an executable cached before a scope was named is served to
the program that names it, WITHOUT the names, and a profile of it reads
as the old program (seen on the CPU backend and on the v5e, PR 25: the
per-kernel metrics of benchmarks/ read nothing from such a cache).  The
price: a key now moves with the line numbers and the checkout's path of
the files a kernel is traced from, so an edit above a kernel, or another
checkout, compiles again where it used to hit.

Nothing else in the repository touches ``jax_compilation_cache_dir``
(``__graft_entry__._disable_compile_cache`` turns the cache OFF for one
run; it places nothing).  Call :func:`enable` before the first compile:
jax latches its cache decision on first use.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.abspath(
    os.path.join(
        os.path.dirname(__file__), os.pardir, os.pardir, "tests", ".jax_cache"
    )
)

# Harnesses that SIGKILL their children (e2e runner, chaos, soak) point
# those children here through ENV_VAR: jax writes cache entries
# non-atomically, and an entry torn by a kill can crash the next reader,
# which must never happen to the directory tier-1 depends on.
HARNESS_DIR = DEFAULT_DIR + "_chaos"


def enable() -> str:
    """Apply the rule above; returns the directory in effect."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return jax.config.jax_compilation_cache_dir
