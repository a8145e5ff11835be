"""A percentile of the benchmark's own host spans or per-request stamps
(ms). Source: program span (the benchmark's span around a call into the
program) or program counter (the program's request stamps).

spec: "span": a span name -> its durations; or "counter": a list of
milliseconds in the counters; "percentile": 50, 95, ..."""

import traffic_gen


def read(ctx, spec):
    if "span" in spec:
        vals = [d * 1e3 for d in ctx.out.spans.durations(spec["span"])]
    else:
        vals = list(ctx.out.counters.get(spec["counter"], []))
    if not vals:
        return None
    return traffic_gen.percentile_nearest_rank(vals, spec["percentile"])
