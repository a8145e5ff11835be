"""Find the knee once: the open-loop cell at several fixed rates, in one
process. For each rate: TTFT and TPOT tails, completions a second and the
backlog left when the window closed (a backlog that grows with the rate
of arrival marks the knee). Run on the chip:

    python3 chipbench/tools/sweep.py --workload <open-loop cell> \\
        --rates 1.5,1.8,2.0,2.3 --seconds 40 --seed 5
"""

import argparse
import copy
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    import harness
    from drivers import serve_engine

    base = harness.load_cell(ROOT, args.workload)
    harness.setup_compile_cache(ROOT)
    devices = harness.require_tpu(base.chips)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.traffic["requests"]["arrivals"]["rate_rps"] = rate
        out = serve_engine.run(cell, seed=args.seed + i,
                               seconds=args.seconds, trace=False,
                               devices=devices, t_proc=time.perf_counter(),
                               root=ROOT)
        c = out.counters
        q = sorted(c["queue_wait_ms"])
        print(json.dumps({
            "rate_rps": rate, "due": out.attempted, "failed": out.failed,
            "ttft_p95_ms": out.metrics.get("ttft_p95_ms"),
            "tpot_p95_ms": out.metrics.get("tpot_p95_ms"),
            "queue_wait_p50_ms": q[len(q) // 2] if q else None,
            "queue_wait_max_ms": q[-1] if q else None,
            "finished_per_s": c["requests_per_s"],
            "backlog_at_close": c["backlog_at_close"],
            "iterations": c["iterations"],
            "correct": harness.is_correct(out.compared),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
