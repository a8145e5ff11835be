"""A kernel's share of its roofline (%) where the cost comes from the
cell's model type: the least time the chip could take for the work the
window did (``model_types/<model_type>.LEAST_SECONDS[cost]``, from the
run's counters and the published peaks), over the device time of the
kernel's events. Source: device trace and program counter. Returns
nothing where the configuration names no model type of the benchmark's,
the counters lack what the cost reads, or the kernel's events are not in
the trace.

spec: "kernels": regular expressions of the kernel's op names; "cost": a
key of the model module's LEAST_SECONDS."""

import importlib

import trace_reduce as tr


def read(ctx, spec):
    if ctx.trace is None:
        return None
    try:
        model = importlib.import_module(
            f"model_types.{ctx.cell.config.get('model_type')}")
    except ImportError:
        return None
    least = model.LEAST_SECONDS[spec["cost"]](ctx.out.dims,
                                              ctx.out.counters, ctx.peaks)
    if not least:
        return None
    shares = []
    for _, lines in sorted(ctx.trace.devices.items()):
        ev = tr.select(lines["ops"], spec["kernels"])
        dev_s = sum(d for _, _, d in ev) / 1e9
        if dev_s > 0:
            shares.append(100.0 * least / dev_s)
    if not shares:
        return None
    return sum(shares) / len(shares)
