"""A kernel's share of its roofline (%): the least time the chip could
take for the calls seen in the trace — the larger of operations over the
peak FLOP/s and bytes over the peak bytes/s, from flops.py — over the
device time of the kernel's events. Source: device trace. Returns
nothing where the kernel's events are not in the trace.

spec: "kernels": substrings of the kernel's op names; "cost":
"flash_fwd" | "flash_bwd" (per call, from the cell's shapes; the bound is
per call, so a kernel that remat runs twice is judged on each run) or
"paged_decode" (from the live contexts of the window's decode rounds);
"events_per_call": how many op events one call makes."""

import flops
import trace_reduce as tr


def read(ctx, spec):
    if ctx.trace is None:
        return None
    c, dims = ctx.out.counters, ctx.out.dims
    shares = []
    for _, lines in sorted(ctx.trace.devices.items()):
        ev = tr.select(lines["ops"], spec["kernels"])
        if not ev:
            continue
        dev_s = sum(d for _, _, d in ev) / 1e9
        if spec["cost"] == "paged_decode":
            least = sum(flops.roofline_seconds(
                *flops.paged_decode_cost(dims, ctxs), ctx.peaks)[0]
                for ctxs in c["decode_contexts"])
        else:
            fn = {"flash_fwd": flops.flash_fwd_cost,
                  "flash_bwd": flops.flash_bwd_cost}[spec["cost"]]
            per_call, _ = flops.roofline_seconds(
                *fn(dims, c["sequences_per_chip"], c["seq_len"],
                    c["heads_per_chip"]), ctx.peaks)
            least = per_call * len(ev) / spec["events_per_call"]
        if dev_s > 0 and least > 0:
            shares.append(100.0 * least / dev_s)
    if not shares:
        return None
    return sum(shares) / len(shares)
