"""The readings the limits of ``correct`` were set from: for each seed the
program's numbers against the plain reference's, and the int8 control's
(``--detail 1``, training: also int8_fwd's and the half-targets fault's),
in one process (set-up is long). In a training cell every side goes
through the comparison a run goes through and carries its ``correct``.
Run on the chip:

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control 1] [--seconds 6] [--out chiprun_out/readings.jsonl]

Prints one JSON line per seed. Not part of a benchmark run.
"""

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--detail", type=int, default=0)
    args = ap.parse_args()
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    harness.setup_compile_cache(ROOT)
    devices = harness.require_tpu(cell.chips)
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if hasattr(driver, "readings"):
            row = driver.readings(cell, seed, devices, bool(args.control),
                                  detail=bool(args.detail))
        else:
            out = driver.run(cell, seed=seed, seconds=args.seconds,
                             trace=False, devices=devices, t_proc=t0,
                             root=ROOT, control=bool(args.control))
            row = {"seed": seed,
                   "program": {n: v for n, v, _ in out.compared},
                   "metrics": out.metrics,
                   "control": out.counters.get("control"),
                   "tokens_compared": out.counters.get("tokens_compared")}
        row["wall_s"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
