"""Cut a small piece out of a profiler trace for the reduction's test:
the ops, modules and benchmark spans of the first ``--modules`` module
events of one device, with what the reduction gives for them.

    python3 chipbench/tools/record_trace.py <file.xplane.pb> <out.json> \\
        [--modules 1]

The trace is any ``.xplane.pb`` that ``jax.profiler.start_trace`` wrote
(a run's own is read and deleted: ``harness.TraceWindow``).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--modules", type=int, default=1)
    args = ap.parse_args()
    import trace_reduce as tr

    trace = tr.load_xplane(args.xplane)
    dev, lines = sorted(trace.devices.items())[0]
    big = max(tr.sum_by_name(lines["modules"]).items(),
              key=lambda kv: kv[1])[0]
    mods = [m for m in lines["modules"] if m[0] == big][1:1 + args.modules]
    t0, t1 = mods[0][1], mods[-1][1] + mods[-1][2]
    ops = [e for e in lines["ops"] if t0 <= e[1] and e[1] + e[2] <= t1]
    spans = [s for s in trace.spans if s[1] < t1 and s[1] + s[2] > t0]
    cut = tr.Trace(devices={dev: {"modules": mods, "ops": ops}},
                   spans=spans)
    o0, o1 = min(s for _, s, _ in ops), max(s + d for _, s, d in ops)
    kernels = {}
    for name in ("custom-call:tpu_custom_call", "fusion"):
        kernels[name] = sum(d for _, _, d in tr.select(ops, [name]))
    rec = {"what": f"{len(ops)} op events of {args.modules} step(s) of "
                   f"{big} on {dev}, recorded on the chip",
           "trace": cut.to_json(),
           "expected": {"busy_ns": tr.busy_ns(ops), "window_ns": o1 - o0,
                        "n_modules": len(mods), "kernel_ns": kernels}}
    with open(args.out, "w") as f:
        json.dump(rec, f)
    print(f"wrote {args.out}: {len(ops)} ops, "
          f"{os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
