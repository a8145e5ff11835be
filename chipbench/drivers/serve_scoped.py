"""Driver ``serve_scoped``: ``drivers/serve_model``'s run with the named
scopes read from the traffic file, and the window's prefill chunks
counted.

``drivers/serve_model.py`` fixes its ``SCOPES`` in code (the routed
layers' and the two attention kinds'), so a model type whose layers run
under other scopes names them in its traffic file, under ``"scopes"``,
and takes this driver: ``counters["op_scopes"]`` of a traced run is then
the program's map from device op to those scopes
(``readers/scope_share.py``, ``readers/scope_roofline.py``). Everything
else is ``serve_model``'s by import: the model type's module as the
yardstick, ``drivers/serve_engine``'s loop, window, sampling and
comparison, the routed layers' counters where the program has any
(``Engine.moe_counters`` gives ``{}`` for a model without, and nothing is
added).

On top: ``counters["prefill_chunks"]``, one ``(start, tokens)`` for each
prompt chunk the window's iterations processed, in order. The loop
computes a chunk's operations through the yardstick's ``prefill_flops``;
this driver hands it a yardstick that notes each call, and the window
takes the calls made between its two ends. What a per-chunk roofline
(``model_types/<type>.LEAST_SECONDS``) counts its bytes from.

Traffic file keys: ``drivers/serve_engine``'s, and ``scopes``: the names
of the program's ``jax.named_scope``s a traced run asks the engine for.
"""

from __future__ import annotations

import harness
from drivers import serve_engine as base
from drivers import serve_model

CONTROLS = base.CONTROLS
# the class itself: ``harness.TraceWindow`` names the run's window while
# a run is on
TraceWindow = harness.TraceWindow


class NotingChunks:
    """A model type's module, with every ``prefill_flops`` call noted."""

    def __init__(self, model):
        self._model = model
        self.chunks: list = []           # (start, tokens), in call order

    def __getattr__(self, name):
        return getattr(self._model, name)

    def prefill_flops(self, dims, start, n_tokens, last):
        self.chunks.append((int(start), int(n_tokens)))
        return self._model.prefill_flops(dims, start, n_tokens, last)


def run(cell, *, seed, seconds, trace, devices, t_proc, root, fault=None,
        control=False) -> harness.RunOutput:
    model = serve_model.model_of(cell)
    yardstick = NotingChunks(model)
    scopes = tuple(cell.traffic.get("scopes", ()))
    windows = []

    class RunWindow(serve_model.Window):
        def __init__(self, enabled):
            super().__init__(enabled)
            windows.append(self)
            self.first_chunk = self.end_chunk = 0

        def start(self) -> None:
            self.first_chunk = len(yardstick.chunks)
            super().start()

        def stop(self) -> None:
            super().stop()
            self.end_chunk = len(yardstick.chunks)

        def load(self):
            scopes_of = getattr(self.engine, "op_scopes", None)
            if self.enabled and scopes and scopes_of is not None:
                self.extra["op_scopes"] = scopes_of(scopes)
            return TraceWindow.load(self)

    def hook(eng):
        RunWindow.engine = eng
        if fault is not None:
            fault(eng)

    with serve_model.bound(base, weights=model, flops=yardstick,
                           reference=model,
                           transformer_config=model.transformer_config), \
            serve_model.bound(harness, TraceWindow=RunWindow):
        out = base.run(cell, seed=seed, seconds=seconds, trace=trace,
                       devices=devices, t_proc=t_proc, root=root,
                       fault=hook, control=control)
    win = windows[0]
    out.counters.update(win.extra)
    out.counters["prefill_chunks"] = yardstick.chunks[
        win.first_chunk:win.end_chunk]
    out.counters.update(serve_model.moe_window(win._before, win._after,
                                               model, out.dims))
    return out
