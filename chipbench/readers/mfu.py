"""The whole step's share of the chips' peak (%): the operations the
mathematics needs (flops.py; recomputation never counted) for the work
finished in the window, over the window, over chips x the published bf16
peak. Source: the benchmark's own counters and the host clock.

spec: "flops": counter names whose sum is the window's operations, or
"steps_times": a per-step counter multiplied by counters["steps"]."""


def read(ctx, spec):
    c = ctx.out.counters
    if "steps_times" in spec:
        total = c[spec["steps_times"]] * c["steps"]
    else:
        total = sum(c[k] for k in spec["flops"])
    if total <= 0 or ctx.out.window_s <= 0:
        return None
    return 100.0 * total / ctx.out.window_s / (
        ctx.n_chips * ctx.peaks["bf16_flops"])
