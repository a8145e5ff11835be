"""One part of the device idle inside the program's spans of one name
(ms): a percentile over those spans, averaged over the cell's chips.
Each span is clipped to the device's first and last event, and its idle
(the gaps of the op line, or of the module line where there are no ops,
as ``host_exposed`` takes them, on the profiler's one clock) is cut in
three:

- ``launch``: from the span's start to the first op that starts inside
  it, where the device was idle when the span opened;
- ``fetch``: from the end of the last op that ends inside the span to
  the span's end; where no op runs inside the span at all, the result
  was ready before the wait began, and the whole span is fetch;
- ``holes``: the rest, the device's own gaps between ops.

The three sum, span by span, to ``host_exposed.overlap_ns`` of that
span. Source: program span and device trace. Returns nothing where the
trace holds no such span.

spec: "span": the span's name; "part": "launch", "holes" or "fetch";
"percentile": 50, 95, ..."""

import bisect

import trace_reduce as tr
import traffic_gen

PARTS = ("launch", "holes", "fetch")


def split(gaps, ends, s: int, e: int) -> tuple:
    """(launch, holes, fetch) ns of the idle inside [s, e). ``gaps``:
    sorted disjoint [a, b) of the device's idle; ``ends``: their b's."""
    inside = []
    i = bisect.bisect_right(ends, s)
    while i < len(gaps) and gaps[i][0] < e:
        a, b = gaps[i]
        inside.append((max(a, s), min(b, e)))
        i += 1
    if not inside:
        return 0, 0, 0
    if inside[0] == (s, e):                  # no op inside the span
        return 0, 0, e - s
    launch = inside[0][1] - s if inside[0][0] == s else 0
    fetch = e - inside[-1][0] if inside[-1][1] == e else 0
    idle = sum(b - a for a, b in inside)
    return launch, idle - launch - fetch, fetch


def read(ctx, spec):
    if ctx.trace is None:
        return None
    part = PARTS.index(spec["part"])
    vals = []
    for _, lines in sorted(ctx.trace.devices.items()):
        ev = lines["ops"] or lines["modules"]
        if not ev:
            continue
        t0 = min(s for _, s, _ in ev)
        t1 = max(s + d for _, s, d in ev)
        mine = tr.clip([e for e in ctx.trace.spans if e[0] == spec["span"]],
                       t0, t1)
        if not mine:
            continue
        gaps = tr.idle_gaps(ev, t0, t1)
        ends = [b for _, b in gaps]
        ms = [split(gaps, ends, s, s + d)[part] / 1e6 for _, s, d in mine]
        vals.append(traffic_gen.percentile_nearest_rank(
            ms, spec["percentile"]))
    if not vals:
        return None
    return sum(vals) / len(vals)
