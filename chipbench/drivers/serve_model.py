"""Driver ``serve_model``: ``drivers/serve_engine``'s run for a model type
that brings its own yardstick.

The loop, the window, the sampling of finished requests and the
comparison are ``drivers/serve_engine``'s, by import: this module binds
the model-specific parts into it for the length of one run. They are a
module ``model_types/<model_type>.py`` (``model_type`` from the
configuration file), which gives what ``weights.py``, ``flops.py``,
``reference.py`` and ``drivers/common.transformer_config`` give for
StarCoder2, under the same names and signatures: ``Dims.from_config``,
``make_params``, ``prefill_flops``, ``serve_token_flops``,
``sequence_logits``, ``transformer_config``. The next model type adds one
such module and no driver (model_types/README.md).

On top, for models with routed experts: the program's counters
(``Engine.moe_counters``: summed on the device, fetched here when the
window opens and when it closes, never inside a turn) become
``counters["moe"]`` (the window's tokens routed, assignments on held
experts, experts touched), ``counters["routed_flops"]`` and
``counters["moe_load_imbalance"]``; and a traced run keeps the program's
map from device op to named scope (``Engine.op_scopes``: ``moe_route``,
``moe_experts``, ``moe_shared``, ``moe_combine``, ``attn_sliding``,
``attn_full``) as ``counters["op_scopes"]``, since the profiler names a
device op by its instruction and drops the scope
(``readers/scope_share.py`` lays the map over the trace).

Traffic file keys: ``drivers/serve_engine``'s.
"""

from __future__ import annotations

import contextlib
import importlib

import harness
from drivers import serve_engine as base

CONTROLS = base.CONTROLS
SCOPES = ("moe_route", "moe_experts", "moe_shared", "moe_combine",
          "attn_sliding", "attn_full")


def model_of(cell):
    return importlib.import_module(f"model_types.{cell.config['model_type']}")


@contextlib.contextmanager
def bound(module, **names):
    """``module``'s globals of these names, replaced for the block."""
    old = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def moe_window(before: dict, after: dict, model, dims) -> dict:
    """What the routed layers did between two fetches of the program's
    counters."""
    if not after:
        return {}
    per_expert, total = [], {"tokens_routed": 0, "held_assignments": 0,
                             "experts_touched": 0}
    for layer, a in after.items():
        b = before[layer]
        for k in total:
            total[k] += a[k] - b[k]
        per_expert.append([x - y for x, y in zip(
            a["tokens_per_held_expert"], b["tokens_per_held_expert"])])
    out = {"moe": dict(total, tokens_per_held_expert=per_expert),
           "routed_flops": (model.assignment_flops(dims)
                            * total["held_assignments"])}
    busiest = [max(row) * len(row) / sum(row) for row in per_expert
               if sum(row)]
    if busiest:
        out["moe_load_imbalance"] = busiest      # one a routed layer
    return out


class Window(harness.TraceWindow):
    """The run's window, with the program's counters fetched at its two
    ends and, in a traced run, its map from device op to scope."""

    engine = None            # set by the fault hook, before the first step

    def __init__(self, enabled: bool):
        super().__init__(enabled)
        self.extra: dict = {}
        self._before = None

    def start(self) -> None:
        self._before = self.engine.moe_counters()
        super().start()

    def stop(self) -> None:
        super().stop()
        self._after = self.engine.moe_counters()

    def load(self):
        scopes_of = getattr(self.engine, "op_scopes", None)
        if self.enabled and scopes_of is not None:
            self.extra["op_scopes"] = scopes_of(SCOPES)
        return super().load()


def run(cell, *, seed, seconds, trace, devices, t_proc, root, fault=None,
        control=False) -> harness.RunOutput:
    model = model_of(cell)
    windows = []

    class RunWindow(Window):
        def __init__(self, enabled):
            super().__init__(enabled)
            windows.append(self)

    def hook(eng):
        RunWindow.engine = eng
        if fault is not None:
            fault(eng)

    with bound(base, weights=model, flops=model, reference=model,
               transformer_config=model.transformer_config), \
            bound(harness, TraceWindow=RunWindow):
        out = base.run(cell, seed=seed, seconds=seconds, trace=trace,
                       devices=devices, t_proc=t_proc, root=root,
                       fault=hook, control=control)
    win = windows[0]
    out.counters.update(win.extra)
    out.counters.update(moe_window(win._before, win._after, model, out.dims))
    return out
