"""The share of its roofline (%) of the work the program runs under some
named scopes of one jitted program: the least time the chip could take
for what the window did there (``model_types/<model_type>.LEAST_SECONDS
[cost]``, from the run's counters and the published peaks) over the
device time of the ops under those scopes in the modules whose name
matches. It reads the same work whatever implements it, a Pallas kernel
or XLA's fusions: the program's map from device op to scope
(``counters["op_scopes"]``, see ``readers/scope_share.py``) says which
ops count, not a kernel's name. Source: device trace and program
counter. Returns nothing where the run kept no such map (an untraced
run, a program or a driver without one), the model type has no such
cost, the counters lack what the cost reads, or no op of the scopes is in
the trace.

spec: "scopes": the scope names; "module": a regular expression of the
jitted programs' names as the map has them (``jit_decode_step``);
"cost": a key of the model module's LEAST_SECONDS."""

import importlib
import re

from readers.scope_share import scope_ns


def read(ctx, spec):
    op_scopes = ctx.out.counters.get("op_scopes")
    if ctx.trace is None or not op_scopes:
        return None
    try:
        model = importlib.import_module(
            f"model_types.{ctx.cell.config.get('model_type')}")
    except ImportError:
        return None
    cost = getattr(model, "LEAST_SECONDS", {}).get(spec["cost"])
    if cost is None:
        return None
    least = cost(ctx.out.dims, ctx.out.counters, ctx.peaks)
    # an op event counts only inside a module the map names
    of_modules = {name: ops for name, ops in op_scopes.items()
                  if re.search(spec["module"], name)}
    if not least or not of_modules:
        return None
    shares = []
    for _, lines in sorted(ctx.trace.devices.items()):
        ns = scope_ns(lines, of_modules, set(spec["scopes"]))
        if ns > 0:
            shares.append(100.0 * least / (ns / 1e9))
    if not shares:
        return None
    return sum(shares) / len(shares)
