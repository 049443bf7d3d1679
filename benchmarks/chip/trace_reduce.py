"""From a profiler trace to device busy time, kernel time and ``breakdown``.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records: per device, the intervals in which an operation ran and the
intervals of each XLA module; on the host, the ``TraceAnnotation`` spans.
The functions after it work on those records only, so a test can check
them on a recorded trace or on intervals written by hand.

- Busy time is the union of a device's operation intervals inside the
  window, so overlapping operations count once; idle is the rest.
- Kernel time is the summed duration of a module's events, by the
  module's stable name (the jitted function's name, ``jit_<fn>``).
- An idle gap is named by the innermost host span open over it, so the
  breakdown says what the host was doing while the chip waited.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # [start, end) in nanoseconds

#: lines of a device plane that hold operations, and modules
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    #: device plane name -> operation intervals
    ops: Dict[str, List[Tuple[str, int, int]]]
    #: device plane name -> (module name, start, end)
    modules: Dict[str, List[Tuple[str, int, int]]]
    #: host spans: (name, start, end)
    spans: List[Tuple[str, int, int]]


def stable_name(name: str) -> str:
    """``jit_quantize_ref(123)`` -> ``jit_quantize_ref``; ``fusion.12`` -> ``fusion``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    return re.sub(r"\.\d+$", "", name)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{log_dir}: {len(paths)} xplane files, expected 1")
    return paths[0]


def load(path: str, span_names: Iterable[str]) -> Trace:
    """Read a trace; keep host spans whose names are in ``span_names``."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    data = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                evs = dest.setdefault(plane.name, [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return Trace(ops=ops, modules=modules, spans=spans)


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` inside [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_gaps(
    idle: Sequence[Interval], spans: Sequence[Tuple[str, int, int]], outer: str
) -> Dict[str, float]:
    """Idle seconds by the innermost host span open over them; time no
    span but ``outer`` covers is named ``outer``."""
    out: Dict[str, float] = {}
    for gs, ge in idle:
        cuts = sorted({gs, ge, *(t for _, s, e in spans for t in (s, e) if gs < t < ge)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            over = [(e - s, n) for n, s, e in spans if s <= mid < e]
            name = min(over)[1] if over else outer
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Trace, window: str = "window") -> Optional[dict]:
    """Busy and idle of each device over the host span named ``window``,
    kernel time by module, and the breakdown. ``None`` where the trace
    holds no window or no device."""
    wins = [(s, e) for n, s, e in trace.spans if n == window]
    if not wins or not trace.ops:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    window_s = (hi - lo) / 1e9
    busy: Dict[str, float] = {}
    idle_named: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    for plane, evs in trace.ops.items():
        merged = union(((s, e) for _, s, e in evs), lo, hi)
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        inner = [sp for sp in trace.spans if sp[0] != window]
        for k, v in name_gaps(gaps(merged, lo, hi), inner, window).items():
            idle_named[k] = idle_named.get(k, 0.0) + v / len(trace.ops)
    for plane, evs in (trace.modules or trace.ops).items():
        for name, s, e in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = stable_name(name)
                op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9 / len(trace.ops)
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "kernel_s": op_time,
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(idle_named)},
    }
