"""Run one cell on the CPU with the look for a chip skipped (tests only).

    JAX_PLATFORMS=cpu python3 bench/tests/cpu_run.py <cell> <seed> <seconds> [fault]

``fault`` is a name of ``bench.faults.FAULTS``.  Prints the same result line
as ``bench/run.py``.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    sys.path.insert(0, ROOT)
    from bench.faults import FAULTS
    from bench.harness import run_cell
    cell, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    fault = FAULTS[sys.argv[4]] if len(sys.argv) > 4 else None
    trace = os.environ.get("BENCH_TEST_TRACE") == "1"
    return run_cell(cell, seed, seconds, trace, t_start=T_START,
                    require_chip=False, prepare=fault)


if __name__ == "__main__":
    sys.exit(main())
