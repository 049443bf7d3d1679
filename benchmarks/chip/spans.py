"""Each window update's interval, found from the program's own spans.

The update to version k runs from the earliest start of an ``unpublish``
span with ``version == k - 1`` (a trainer shard retiring the version
before) to the latest end of an ``update`` span with ``version == k``
(the last rollout to hold k). The per-layer metrics that read program
spans take, in each such interval, the wall time their spans cover (the
union of their intervals, so threads working at once count once) and
average it over the window's updates.

A program without these spans, or without ``repro.obs.wall_seconds``,
gives nothing to read: every function here then returns ``None``.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

try:
    from repro.obs.telemetry import wall_seconds
except ImportError:  # a program older than its stage spans
    wall_seconds = None


def update_intervals(events, versions: Iterable[int]) -> Optional[List[Tuple[float, float]]]:
    """``(lo, hi)`` of the update to each version, in order; ``None``
    where any of them lacks its ``unpublish`` or ``update`` span."""
    starts: Dict[int, float] = {}
    ends: Dict[int, float] = {}
    for name, _, t0, t1, _, attrs in events:
        v = (attrs or {}).get("version")
        if v is None:
            continue
        if name == "unpublish":
            starts[v + 1] = min(t0, starts.get(v + 1, t0))
        elif name == "update":
            ends[v] = max(t1, ends.get(v, t1))
    out = []
    for k in versions:
        if k not in starts or k not in ends:
            return None
        out.append((starts[k], ends[k]))
    return out


def _window(ctx):
    """The recorder's finished spans and the window's update intervals,
    or ``None`` where there is nothing to read."""
    rec = ctx["cell"].recorder
    if rec is None or wall_seconds is None:
        return None
    events = list(rec.events)
    ivs = update_intervals(events, [u["version"] for u in ctx["updates"]])
    if not ivs:
        return None
    return events, ivs


def _inside(events, names, ivs):
    return [e for e in events if e[0] in names and any(lo <= e[2] < hi for lo, hi in ivs)]


def mean_wall_seconds(ctx, names: Tuple[str, ...]) -> Optional[float]:
    """Mean over the window's updates of the wall seconds the named spans
    cover inside each update; ``None`` where none of them ran there."""
    got = _window(ctx)
    if got is None:
        return None
    events, ivs = got
    if not _inside(events, names, ivs):
        return None
    return sum(wall_seconds(events, names, lo, hi) for lo, hi in ivs) / len(ivs)


def mean_attr_sum(ctx, name: str, attr: str) -> Optional[float]:
    """Mean over the window's updates of the sum of ``attr`` over the
    spans called ``name`` that start inside each update; ``None`` where
    none did."""
    got = _window(ctx)
    if got is None:
        return None
    events, ivs = got
    mine = _inside(events, (name,), ivs)
    if not mine:
        return None
    return sum((e[5] or {}).get(attr, 0) for e in mine) / len(ivs)
