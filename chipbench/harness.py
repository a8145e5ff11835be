"""The parts every cell shares: finding a cell's files by name, the look
for the chip, the compile cache, host spans, the profiler window, the
per-layer readers and the result line.

Nothing here names a cell, a configuration, a traffic mix or a metric: a
cell is whatever ``BENCHMARK.json`` lists, and its pieces are files found
by name under the benchmark's directory —
``configs/<config>.json``, ``traffic/<traffic>.json`` (names its driver:
a module under ``drivers/``), ``checks/<cell>.json`` (the limits of
``correct``), ``layer_metrics/<metric>.json`` (names its reader: a module
under ``readers/``) and ``peaks.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

from trace_reduce import SPAN_PREFIX

HERE = os.path.dirname(os.path.abspath(__file__))
EXIT_NO_CHIP = 3


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    checks: dict
    bench: dict
    bench_dir: str

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(root: str, name: str, bench_dir: str = HERE) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = rows[0]
    cfg_row = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    checks_path = os.path.join(bench_dir, "checks", f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=load_json(os.path.join(root, cfg_row["file"])),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       f"{w['traffic']}.json")),
        checks=load_json(checks_path), bench=bench, bench_dir=bench_dir)


def load_peaks(device_kind: str, bench_dir: str = HERE) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json; add them with "
                       f"their source, there is no default")
    return table[device_kind]


# -- the chip ------------------------------------------------------------------

def setup_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed place: where
    JAX_COMPILATION_CACHE_DIR says if it is set (and then no directory is
    set in code), else <checkout>/.chipbench_cache/jax. Every program is
    cached, however fast it compiled."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(root, ".chipbench_cache", "jax")
    if not placed:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_tpu(chips: int):
    """The devices the cell asks for, or exit without a result."""
    import jax

    try:
        devs = jax.devices()
    except Exception as e:  # noqa: BLE001 - no backend, no run
        print(f"[chipbench] no accelerator: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        raise SystemExit(EXIT_NO_CHIP)
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[chipbench] the cell asks for {chips} TPU chip(s); JAX "
              f"reports {len(devs)} x {devs[0].platform!r}",
              file=sys.stderr, flush=True)
        raise SystemExit(EXIT_NO_CHIP)
    return devs[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        s = d.memory_stats()
        if s:
            peaks.append(int(s.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# -- spans and the traced window ----------------------------------------------

class Spans:
    """Host spans of the benchmark's own, on the host clock and (through
    TraceAnnotation) in the profiler's trace."""

    def __init__(self):
        self.rows: list = []           # (name, start_s, dur_s)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter() - t0))

    def rename_last(self, name: str) -> None:
        _, t0, d = self.rows[-1]
        self.rows[-1] = (name, t0, d)

    def durations(self, name: str) -> list:
        return [d for n, _, d in self.rows if n == name]


class TraceWindow:
    """Profiles from start() to stop(), then reads the trace back."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.trace = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.enabled:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self):
        if not self.enabled:
            return None
        import trace_reduce

        self.trace = trace_reduce.load_xplane(
            trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


# -- what a driver hands back ---------------------------------------------------

@dataclasses.dataclass
class RunOutput:
    metrics: dict                    # every end-to-end value it can give
    attempted: int
    failed: int
    window_s: float
    counters: dict                   # program and benchmark counts
    spans: Spans
    dims: object
    compared: list = dataclasses.field(default_factory=list)
    # (name, value, limit): correct iff every value <= limit
    trace: object = None
    trace_window_s: float | None = None
    memory_peak: int = 0


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader sees."""

    cell: Cell
    out: RunOutput
    peaks: dict
    n_chips: int

    @property
    def trace(self):
        return self.out.trace


def read_layer_metrics(ctx: ReadContext) -> dict:
    out = {}
    for m in ctx.cell.per_layer():
        spec = load_json(os.path.join(ctx.cell.bench_dir, "layer_metrics",
                                      f"{m['name']}.json"))
        mod = importlib.import_module(f"readers.{spec['reader']}")
        value = mod.read(ctx, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_busy(trace, t_window_ns=None) -> tuple:
    """(busy seconds averaged over the devices, traced seconds): busy is
    the union of the intervals in which an op ran on the device; the
    window runs from the first to the last device event of any chip."""
    import trace_reduce as tr

    if trace is None or not trace.devices:
        return 0.0, 0.0
    starts, ends, busy = [], [], []
    for lines in trace.devices.values():
        ev = lines["ops"] or lines["modules"]
        if not ev:
            continue
        starts.append(min(s for _, s, _ in ev))
        ends.append(max(s + d for _, s, d in ev))
        busy.append(tr.busy_ns(ev))
    if not busy:
        return 0.0, 0.0
    n = len(trace.devices)
    return sum(busy) / n / 1e9, (max(ends) - min(starts)) / 1e9


def breakdown(trace) -> dict:
    import trace_reduce as tr

    if trace is None or not trace.devices:
        return {}
    dev, lines = sorted(trace.devices.items())[0]
    ev = lines["ops"] or lines["modules"]
    if not ev:
        return {}
    t0 = min(s for _, s, _ in ev)
    t1 = max(s + d for _, s, d in ev)
    return {"device_ops": tr.top_ops(ev),
            "idle_gaps": tr.attribute_gaps(tr.idle_gaps(ev, t0, t1),
                                           trace.spans)}


def is_correct(compared: list) -> bool:
    """Something was compared and every value lies within its limit (a
    NaN lies within none)."""
    return bool(compared) and all(v == v and v <= lim
                                  for _, v, lim in compared)


def run_cell(root: str, cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_proc: float, fault=None) -> dict:
    """Everything after the look for the chip: the driver's set-up,
    window and comparison, then the result object. ``fault`` (the
    benchmark's own tests) is handed the trainer or the engine before
    its first step, to break the timed path underneath."""
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    out: RunOutput = driver.run(cell, seed=seed, seconds=seconds,
                                trace=trace, devices=devices, t_proc=t_proc,
                                root=root, fault=fault)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak}
    result = {"attempted": out.attempted, "failed": out.failed}
    if trace:
        busy_s, window_s = device_busy(out.trace)
        device["busy_s"], device["window_s"] = busy_s, window_s
        result["metrics"] = {}
        if d0.platform == "tpu":     # never a device metric off the chip
            ctx = ReadContext(cell=cell, out=out, n_chips=len(devices),
                              peaks=load_peaks(d0.device_kind,
                                               cell.bench_dir))
            result["metrics"] = read_layer_metrics(ctx)
        result["breakdown"] = breakdown(out.trace)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        missing = [n for n in units if n not in out.metrics]
        if missing:
            raise RuntimeError(f"driver gave no value for {missing}")
        result["metrics"] = {n: {"value": float(out.metrics[n]), "unit": u}
                             for n, u in units.items()}
    result["device"] = device
    compared = {n: {"value": v, "limit": lim} for n, v, lim in out.compared}
    ordered = {"correct": is_correct(out.compared), **result,
               "compared": compared}
    for n, v, lim in out.compared:
        print(f"[chipbench] compared {n} = {v!r} (limit {lim!r}) "
              f"{'ok' if v == v and v <= lim else 'OVER'}",
              file=sys.stderr, flush=True)
    return ordered
