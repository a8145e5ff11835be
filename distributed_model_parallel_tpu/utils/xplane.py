"""Hardware-profiler trace capture + analysis (no TensorBoard UI needed).

``jax.profiler`` writes XSpace protos (``*.xplane.pb``) containing REAL
device timelines — per-HLO-op start/duration measured by the TPU runtime,
not host wall clock and not XLA cost-analysis estimates. The reference's
observability is host-side ``time.time()`` deltas (``utils.py:41-74``);
this module is the TPU-native upgrade that closes the loop from "we think
this step is bandwidth-bound" to measured per-op device time (retiring
demand-side >1.0 ``hbm_frac_of_peak`` inferences in favor of hardware
counters).

Usage::

    with trace_to("/tmp/trace") as d: run_steps()
    space = load_xspace(d)           # newest *.xplane.pb under d
    plane = device_plane(space)      # "/device:TPU:0"
    mods  = module_events(plane)     # compiled-module executions
    ops   = op_breakdown(plane)      # per-op device time, categorized

The proto schema (XSpace → XPlane → XLine → XEvent with stat key/value
pairs) is public TSL/OpenXLA; parsing uses the ``xplane_pb2`` bindings
shipped with the baked-in tensorflow wheel, with a graceful error when
absent.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable

import jax

_xplane_pb2 = None


class XplaneProtosUnavailable(ImportError):
    """The xplane_pb2 protobuf bindings are not importable.

    Subclasses ImportError so pre-existing ``except ImportError`` callers
    keep working; new callers (the CLI below, scripts/dmp_report.py) catch
    this specifically and print :data:`PROTO_HINT` instead of a traceback.
    """


PROTO_HINT = (
    "xplane trace analysis needs the xplane_pb2 protobuf bindings "
    "(tensorflow.tsl.profiler.protobuf.xplane_pb2, shipped with the "
    "tensorflow wheel); they are not importable here — install tensorflow "
    "(CPU build is enough) or skip the trace-analysis step; trace CAPTURE "
    "(jax.profiler / trace_to) works without them")


def _pb2():
    """Lazy import: tensorflow is heavy and only profiler analysis needs it."""
    global _xplane_pb2
    if _xplane_pb2 is None:
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
        except ImportError as e:        # pragma: no cover - env without tf
            raise XplaneProtosUnavailable(PROTO_HINT) from e
        _xplane_pb2 = xplane_pb2
    return _xplane_pb2


def protos_available() -> bool:
    """True when the xplane_pb2 bindings import (analysis paths will work)."""
    try:
        _pb2()
    except XplaneProtosUnavailable:
        return False
    return True


@contextlib.contextmanager
def trace_to(log_dir: str):
    """Capture a profiler trace; yields ``log_dir`` for later parsing."""
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def load_xspace(log_dir: str):
    """Parse the newest ``*.xplane.pb`` under ``log_dir`` into an XSpace."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    xs = _pb2().XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def device_plane(space, index: int = 0):
    """The ``/device:TPU:<index>`` plane (raises if the trace is
    host-only)."""
    name = f"/device:TPU:{index}"
    for plane in space.planes:
        if plane.name == name:
            return plane
    raise ValueError(
        f"no {name} plane in trace (planes: {[p.name for p in space.planes]})"
        " — device events were not captured")


def plane_peaks(plane) -> dict:
    """Device peaks the profiler itself reports (TFLOP/s, HBM GB/s…) —
    the hardware's own numbers, preferable to our static tables."""
    names = _stat_names(plane)
    out = {}
    for s in plane.stats:
        key = names.get(s.metadata_id, str(s.metadata_id))
        val = _stat_value(s)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            out[key] = val
    return out


def _stat_names(plane) -> dict:
    return {k: v.name for k, v in plane.stat_metadata.items()}


def _stat_value(s):
    """The set oneof value of an XStat — presence-checked, so a legitimate
    zero (e.g. device_offset_ps=0 for the first event) survives instead of
    falling through a truthiness chain to None."""
    which = s.WhichOneof("value")
    return getattr(s, which) if which else None


def _stat(ev, names: dict, name: str):
    for s in ev.stats:
        if names.get(s.metadata_id) == name:
            return _stat_value(s)
    return None


@dataclasses.dataclass
class ModuleEvent:
    name: str
    start_ps: int
    duration_ps: int


def _line(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return line
    return None


def module_events(plane) -> list[ModuleEvent]:
    """Compiled-module executions (one per dispatched program), device time."""
    line = _line(plane, "XLA Modules")
    if line is None:
        return []
    ev_names = {k: v.name for k, v in plane.event_metadata.items()}
    st_names = _stat_names(plane)
    out = []
    for ev in line.events:
        dur = _stat(ev, st_names, "device_duration_ps")
        off = _stat(ev, st_names, "device_offset_ps")
        dur = ev.duration_ps if dur is None else dur
        off = ev.offset_ps if off is None else off
        out.append(ModuleEvent(ev_names.get(ev.metadata_id, "?"),
                               int(off), int(dur)))
    out.sort(key=lambda m: m.start_ps)
    return out


# HLO-instruction-text → category. Fusions are opaque here ("%fusion.3 =
# ... calls=%fused_computation.3"); classify_fusions() resolves them
# against the optimized HLO text when provided.
_CATEGORY_PATTERNS = [
    ("convolution", r"\bconvolution\b"),
    ("matmul", r"\bdot\b|\bcustom-call.*__cublas|\bdot-general\b"),
    ("allreduce", r"\ball-reduce\b|\breduce-scatter\b|\ball-gather\b"
                  r"|\ball-to-all\b|\bcollective-permute\b"),
    ("copy", r"\bcopy\b|\bcopy-start\b|\bcopy-done\b|\btranspose\b"
             r"|\bbitcast\b|\breshape\b"),
    ("custom-call", r"\bcustom-call\b"),
    ("reduce", r"\breduce\b|\breduce-window\b"),
    ("loop-ctrl", r"\bwhile\b|\bconditional\b|\btuple\b"
                  r"|\bget-tuple-element\b"),
    ("infeed-outfeed", r"\binfeed\b|\boutfeed\b|\bsend\b|\brecv\b"),
]


def _category(op_text: str) -> str:
    if " fusion(" in op_text or op_text.startswith("%fusion"):
        return "fusion"
    for cat, pat in _CATEGORY_PATTERNS:
        if re.search(pat, op_text):
            return cat
    return "other"


_FUSION_CALL_RE = re.compile(r"calls=(%?[\w.\-]+)")


def fusion_kinds_from_hlo(hlo_text: str) -> dict[str, str]:
    """Map fused-computation name → dominant content category, from the
    optimized HLO module text (``compiled.as_text()``).

    A fusion containing a convolution is "conv-fusion"; containing a dot,
    "matmul-fusion"; a reduce, "reduce-fusion"; else "elementwise-fusion".
    This is how a flat fusion name in the trace becomes attributable work.
    """
    kinds: dict[str, str] = {}
    current = None
    body: list[str] = []

    def finish():
        if current is None:
            return
        text = "\n".join(body)
        if re.search(r"\bconvolution\b|= \S+ convolution", text):
            kinds[current] = "conv-fusion"
        elif re.search(r"\bdot\(|\bdot-general\b| dot\(", text):
            kinds[current] = "matmul-fusion"
        elif re.search(r"\breduce\(|\breduce-window\b", text):
            kinds[current] = "reduce-fusion"
        elif re.search(r"\bgather\(|\bscatter\(|dynamic-slice", text):
            kinds[current] = "gather-fusion"
        else:
            kinds[current] = "elementwise-fusion"

    for raw in hlo_text.splitlines():
        line = raw.strip()
        first = line.split("(")[0].split()[0] if line else ""
        if line.endswith("{") and first.lstrip("%").startswith("fused"):
            finish()
            current, body = first.lstrip("%"), []
        elif line == "}" and current is not None:
            finish()
            current, body = None, []
        elif current is not None:
            body.append(line)
    finish()
    return kinds


@dataclasses.dataclass
class OpRow:
    name: str          # leading HLO result name, e.g. "%fusion.12"
    category: str
    total_ps: int
    count: int
    example: str       # one full instruction text


def op_breakdown(plane, hlo_text: str | None = None) -> list[OpRow]:
    """Aggregate per-op device time over the whole trace, descending.

    With ``hlo_text`` (the compiled module's optimized HLO), fusion ops are
    re-categorized by their fused content (conv-fusion vs elementwise-…).
    """
    line = _line(plane, "XLA Ops")
    if line is None:
        return []
    ev_names = {k: v.name for k, v in plane.event_metadata.items()}
    st_names = _stat_names(plane)
    fusion_kinds = fusion_kinds_from_hlo(hlo_text) if hlo_text else {}
    agg: dict[str, list] = {}
    for ev in line.events:
        text = ev_names.get(ev.metadata_id, "?")
        dur = _stat(ev, st_names, "device_duration_ps")
        dur = int(ev.duration_ps if dur is None else dur)
        name = text.split(" ", 1)[0].rstrip("=").strip()
        cat = _category(text)
        if cat == "fusion" and fusion_kinds:
            m = _FUSION_CALL_RE.search(text)
            if m:
                cat = fusion_kinds.get(m.group(1).lstrip("%"), "fusion")
        if name not in agg:
            agg[name] = [cat, 0, 0, text]
        agg[name][1] += dur
        agg[name][2] += 1
    rows = [OpRow(n, c, t, k, ex) for n, (c, t, k, ex) in agg.items()]
    rows.sort(key=lambda r: -r.total_ps)
    return rows


def exclude_envelopes(rows: Iterable[OpRow]) -> list[OpRow]:
    """Drop loop/branch ENVELOPE ops (``%while``, ``%conditional``): their
    device duration contains every op executed inside the body, so summing
    them alongside the inner ops double-counts the entire loop. Use before
    category_totals or any roofline aggregation."""
    return [r for r in rows
            if not r.name.startswith(("%while", "%conditional"))]


def category_totals(rows: Iterable[OpRow]) -> dict[str, float]:
    """Device-time totals (seconds) per category, descending.

    Pass ``exclude_envelopes(rows)`` unless you want loop bodies counted
    twice (once inside the ``%while`` envelope, once as themselves)."""
    tot: dict[str, float] = defaultdict(float)
    for r in rows:
        tot[r.category] += r.total_ps / 1e12
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> None:
    """CLI: summarize a jax.profiler trace directory without TensorBoard.

    ``python -m distributed_model_parallel_tpu.utils.xplane /tmp/trace``
    prints the module executions, per-category device time, and the top
    ops — the quick-look the reference's time.time() logging never had.
    """
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("trace_dir", help="directory passed to trace_to / "
                                     "jax.profiler.start_trace")
    p.add_argument("--top", type=int, default=15, help="top ops to print")
    args = p.parse_args(argv)

    try:
        _pb2()
    except XplaneProtosUnavailable as e:
        # Actionable one-liner, no traceback.
        raise SystemExit(f"[xplane] {e}") from None
    plane = device_plane(load_xspace(args.trace_dir))
    peaks = plane_peaks(plane)
    mods = module_events(plane)
    rows = exclude_envelopes(op_breakdown(plane))
    print(f"device peaks: {peaks}")
    mod_s = sum(m.duration_ps for m in mods) / 1e12
    print(f"{len(mods)} module executions, {mod_s:.4f}s device time")
    for cat, sec in category_totals(rows).items():
        print(f"  {cat:24s} {sec * 1e3:10.2f} ms")
    print(f"top {args.top} ops:")
    for r in rows[:args.top]:
        print(f"  {r.total_ps / 1e9:9.3f} ms x{r.count:6d} "
              f"{r.category:18s} {r.name}")


if __name__ == "__main__":   # pragma: no cover - thin CLI shell
    main()
