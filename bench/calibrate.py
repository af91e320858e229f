"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 3] [--fault-seeds 3]
    python bench/calibrate.py --workload <serving cell> --seeds 1 \
        --rate 0.9 --seconds 48           # one point of the knee sweep

In one process, for each seed: the program's numbers (the cell's set-up
through the program, then the plain reference), and on the first few seeds
the control's (the reference in the next precision below the stated one,
put in the program's place) and each planted fault's.  Prints one JSON
line per reading and, last, the largest program reading and the smallest
control and fault readings of each number.  Not run by the benchmark's own
runs; see PERF.md for the readings and the limits set from them.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--rate", type=float,
                    help="serving cells: serve this offered rate for "
                    "--seconds on the cell's own path instead, one set-up "
                    "per seed, and report every window's serving time")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--window-s", type=float,
                    help="serving sweep: windows of this length instead")
    args = ap.parse_args()

    from lib import model
    from lib.harness import Run, kind_module
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(model.ROOT / ".jax_cache"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    spec = model.cell(args.workload)
    drv = kind_module(spec["mix"]["kind"])
    if args.rate:
        spec["mix"] = dict(spec["mix"], rate_rps=args.rate)
        if args.window_s:
            spec["mix"] = dict(spec["mix"], window_s=args.window_s)
        for seed in args.seeds:
            run = Run(spec, seed, 0.0, False, time.perf_counter())
            row = drv.sweep(run, args.seconds)
            row.update(seed=seed, window_s=run.mix["window_s"])
            print(json.dumps(row), flush=True)
        return 0
    rows = []
    for n, seed in enumerate(args.seeds):
        run = Run(spec, seed, 0.0, False, time.perf_counter())
        for row in drv.calibrate(run, control=n < args.control_seeds,
                                 faults=n < args.fault_seeds):
            row["seed"] = seed
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for row in rows:
        for k, v in row["numbers"].items():
            s = summary.setdefault(row["what"], {})
            agg = max if row["what"] == "program" else min
            s[k] = agg(s[k], v) if k in s else v
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
