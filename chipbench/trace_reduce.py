"""From a profiler trace to numbers: device busy time, module and kernel
durations, idle gaps and what the host was doing in them.

The reduction works on plain tuples ``(name, start_ns, dur_ns)`` so that
it can be checked on a small recorded trace (``tests/data``); only
:func:`load_xplane` touches the profiler's file, through
``jax.profiler.ProfileData`` (nothing but JAX is needed). The
categorising follows ``utils/xplane.py`` (module line / op line of each
device plane); that file needs protobuf classes that may be absent, so
the benchmark keeps this copy.
"""

from __future__ import annotations

import dataclasses
import glob
import os

import re

SPAN_PREFIX = "cb:"          # the benchmark's own host spans
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    """device -> {"modules": [...], "ops": [...]}; spans: host spans of
    the benchmark on the profiler's clock. All events are
    ``(name, start_ns, dur_ns)`` sorted by start."""

    devices: dict
    spans: list

    def to_json(self) -> dict:
        return {"devices": self.devices, "spans": self.spans}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(
            devices={d: {k: [tuple(e) for e in v] for k, v in lines.items()}
                     for d, lines in obj["devices"].items()},
            spans=[tuple(e) for e in obj["spans"]])


_BRACES = re.compile(r"\{[^}]*\}")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_DTYPE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*|pred)\[")
_SHAPED = re.compile(r"\b((?:[a-z]+[0-9]+[a-z0-9]*|pred)\[[0-9,]*\])")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str, limit: int = 160) -> str:
    """An op event's name is the whole HLO instruction. Keep what tells
    ops apart: ``%result opcode[:custom-call target] out=(types
    with shapes) in=(dtypes)``. Pallas kernels without a name of their own (the flash
    kernels: ``%checkpoint.19``, ``%closed_call.18``...) can then be told
    by their signature."""
    if " = " not in name:
        return name[:limit]
    head, rest = name.split(" = ", 1)
    rest = _BRACES.sub("", rest)
    m = _OPCODE.search(rest)
    if not m:
        return f"{head} {rest}"[:limit]
    opcode, start = m.group(1), m.end()
    depth, end = 1, start
    while end < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[end], 0)
        end += 1
    outs = ",".join(_SHAPED.findall(rest[:m.start()]))
    ins = ",".join(_DTYPE.findall(rest[start:end]))
    t = _TARGET.search(rest[end:]) if opcode == "custom-call" else None
    op = f"{opcode}:{t.group(1)}" if t else opcode
    return f"{head} {op} out=({outs}) in=({ins})"[:limit]


def is_container(short: str) -> bool:
    parts = short.split(" ", 2)
    return len(parts) > 1 and parts[1] in CONTAINERS


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        pname = plane.name
        if pname.startswith("/device:TPU:") and "Core" not in pname:
            lines = {}
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                cache: dict = {}
                evs = []
                for ev in line.events:
                    n = ev.name
                    if n not in cache:
                        cache[n] = short_name(n)
                    evs.append((cache[n], int(ev.start_ns),
                                int(ev.duration_ns)))
                evs.sort(key=lambda e: e[1])
                lines[key] = evs
            if lines:
                devices[pname] = {"modules": lines.get("modules", []),
                                  "ops": lines.get("ops", [])}
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns),
                                      int(ev.duration_ns)))
    spans.sort(key=lambda e: e[1])
    return Trace(devices=devices, spans=spans)


def describe_xplane(path: str, top: int = 25) -> str:
    """What a trace holds, for a look by hand: planes, lines, event counts
    and the names that took most time on each line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            tot = {}
            n = 0
            for ev in line.events:
                n += 1
                a = tot.setdefault(ev.name, [0, 0])
                a[0] += 1
                a[1] += ev.duration_ns
            out.append(f"  line {line.name!r}: {n} events, "
                       f"{len(tot)} names")
            for name, (c, ns) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {ns / 1e6:10.3f} ms  x{c:<6d} {name[:110]}")
    return "\n".join(out)


# -- reductions ---------------------------------------------------------------

def clip(events, t0: int, t1: int):
    """Events cut to the window [t0, t1) (partial overlaps are trimmed)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_intervals(events):
    """Merged [start, end) intervals covered by any event."""
    out = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if d <= 0:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def busy_ns(events) -> int:
    return sum(b - a for a, b in union_intervals(events))


def idle_gaps(events, t0: int, t1: int):
    """[start, end) stretches of [t0, t1) in which no event ran."""
    gaps, cur = [], t0
    for a, b in union_intervals(clip(events, t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def sum_by_name(events) -> dict:
    tot = {}
    for name, _, d in events:
        tot[name] = tot.get(name, 0) + d
    return tot


def select(events, patterns):
    """Events whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in rx)]


def median(xs):
    import statistics
    return statistics.median(xs) if xs else None


def span_at(spans, t: int) -> str:
    """Innermost benchmark span covering instant ``t`` ("host" if none)."""
    best, best_d = "host", None
    for name, s, d in spans:
        if s > t:
            break
        if s <= t < s + d and (best_d is None or d < best_d):
            best, best_d = name, d
    return best


def attribute_gaps(gaps, spans, top: int = 10):
    """Idle seconds by what the benchmark's spans say the host was doing
    at the middle of each gap; the ``top`` largest, as [name, seconds]."""
    tot = {}
    for a, b in gaps:
        name = span_at(spans, (a + b) // 2)
        tot[name] = tot.get(name, 0) + (b - a)
    return [[n, ns / 1e9] for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(events, top: int = 10):
    """The ops that took most device time, containers (while, call,
    conditional: their bodies are listed themselves) left out."""
    tot = sum_by_name(e for e in events if not is_container(e[0]))
    return [[n, ns / 1e9] for n, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


if __name__ == "__main__":
    import sys
    print(describe_xplane(sys.argv[1]))
