"""Share of the traced device time spent under some of the program's
named scopes (%). The profiler names a device op by its instruction
(``%fusion.12``) and drops the ``op_name`` its scope is written in, so
the program says which instruction of which compiled module runs under
which scope (``counters["op_scopes"]``: {module: {instruction: scope}},
``serve.Engine.op_scopes`` through the driver); an op event belongs to
the module event it starts in. The share is those ops' device time
(containers left out: their bodies' ops are events themselves) over the
device's busy time in the trace. A kernel the compiler renames on the
way (XLA's grouped product keeps no ``op_name`` of the program's) is
counted by its op name instead. Source: device trace. Returns nothing
where the run kept no such map (an untraced run, a driver or a program
without one).

spec: "scopes": the scope names; "ops" (optional): regular expressions
of op names that belong to the scopes whatever the map says."""

import harness
import trace_reduce as tr


def scope_ns(lines, op_scopes, scopes, ops=()) -> int:
    """Device nanoseconds of one device's op events under ``scopes``, or
    named by one of ``ops``."""
    mods = lines["modules"]
    by_name = {e[1:3] for e in tr.select(lines["ops"], ops)} if ops else ()
    total, i = 0, 0
    for name, start, dur in lines["ops"]:
        if (start, dur) in by_name:
            total += dur
            continue
        while i + 1 < len(mods) and mods[i + 1][1] <= start:
            i += 1
        if not mods or not (mods[i][1] <= start < mods[i][1] + mods[i][2]):
            continue
        of_module = op_scopes.get(mods[i][0].split("(")[0], {})
        if (of_module.get(name.split(" ")[0]) in scopes
                and not tr.is_container(name)):
            total += dur
    return total


def read(ctx, spec):
    op_scopes = ctx.out.counters.get("op_scopes")
    if ctx.trace is None or not op_scopes:
        return None
    busy_s, _ = harness.device_busy(ctx.trace)
    per_dev = [scope_ns(lines, op_scopes, set(spec["scopes"]),
                        spec.get("ops", ()))
               for _, lines in sorted(ctx.trace.devices.items())]
    ns = sum(per_dev) / max(1, len(per_dev))
    if busy_s <= 0 or ns <= 0:
        return None
    return 100.0 * ns / 1e9 / busy_s
