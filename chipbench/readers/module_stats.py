"""Statistics of one jitted program's module events on the device plane
(ms). Source: device trace.

spec: "stat": "median_duration" | "median_gap"; "module": how to pick
the program — "largest" (the module name with most device time: the train
step) or {"contains_op": [...]} (the module events inside which an op of
one of those names runs: the decode step holds the paged kernel, and
shares its jitted name with the prefill step)."""

import trace_reduce as tr


def pick_modules(lines, how):
    mods, ops = lines["modules"], lines["ops"]
    if not mods:
        return []
    if how == "largest":
        tot = tr.sum_by_name(mods)
        name = max(tot, key=tot.get)
        return [e for e in mods if e[0] == name]
    marks = sorted(s for _, s, _ in tr.select(ops, how["contains_op"]))
    out, i = [], 0
    for e in mods:
        while i < len(marks) and marks[i] < e[1]:
            i += 1
        if i < len(marks) and marks[i] < e[1] + e[2]:
            out.append(e)
    return out


def read(ctx, spec):
    if ctx.trace is None:
        return None
    vals = []
    for _, lines in sorted(ctx.trace.devices.items()):
        mods = pick_modules(lines, spec["module"])
        if spec["stat"] == "median_duration":
            vals += [d for _, _, d in mods]
        else:
            vals += [b[1] - (a[1] + a[2]) for a, b in zip(mods, mods[1:])]
    if not vals:
        return None
    return tr.median(vals) / 1e6
