"""The precision control of a cell, read on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 [--fault control]

One process, one set-up; then, for each seed, one unit of the cell with
the 8-bit reference in the simulator's place (``bench.faults.control``, or
another entry of ``bench.faults.FAULTS``) and the program's own image
comparison off, compared with the plain reference as a benchmark run would.
Prints one JSON line per seed with the numbers compared.  The benchmark's
own runs never run this.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="control")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np

    from repro.compile_cache import enable_compile_cache

    from bench import harness
    from bench.faults import FAULTS
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    cell = harness.load_cell(args.workload)
    try:
        runner = harness.Runner(cell, args.seeds[0])
        FAULTS[args.fault](runner)
        print(f"control: set-up {time.perf_counter() - T_START:.3f} s on "
              f"{dev.device_kind}", file=sys.stderr)
        for seed in args.seeds:
            runner.rng = np.random.default_rng(seed % (1 << 64))
            runner.recorder.install()
            try:
                window_s = runner.window(0)
            finally:
                runner.recorder.uninstall()
            checks = runner.compare()
            print(json.dumps({"workload": args.workload, "fault": args.fault,
                              "seed": seed, "window_s": window_s,
                              "platform": dev.platform,
                              "kind": dev.device_kind, **checks}),
                  flush=True)
    finally:
        harness.stop_workers()
    return 0


if __name__ == "__main__":
    sys.exit(main())
