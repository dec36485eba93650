#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell of BENCHMARK.json on the TPU this
process is started on.  One process, no child; exits non-zero and
prints no result unless JAX's default device is a TPU.  The last line
of stdout is the result (benchmarks/harness.py)."""

import time

T_START = time.monotonic()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    try:
        import cometbft_tpu  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not here: {e}", file=sys.stderr)
        return 2
    from benchmarks import harness

    return harness.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
