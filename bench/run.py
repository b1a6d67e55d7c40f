"""Run one cell of ``BENCHMARK.json`` on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the lines
before it on standard error give set-up, what compiled inside the window
(nothing should) and each number compared with its limit.  Without an
accelerator, or with fewer chips than the cell asks for, it exits nonzero
and prints no result.

This file imports nothing but the standard library at its top level: the
compile pool's forkserver imports it again in every worker.
"""
import argparse
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.harness import run_cell
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
