"""Low-overhead telemetry recorder: spans, instant events and counters.

The recorder is clock-injected: pass ``time.monotonic`` (default) for
the threaded data plane or ``lambda: env.now`` for the simulator, and
the same instrumentation code produces wall-clock or virtual-time
spans with no other changes.

A recorder on the wall clock also bridges its spans onto the JAX
profiler's clock: while a profiler session is active, every span opens
a ``jax.profiler.TraceAnnotation("tensorhub.<name>")`` on the thread
that opened it and closes it in ``end()``, so program stages appear on
the trace's host plane beside the device's operations. A recorder on an
injected clock (the simulator) never bridges. A span must end on the
thread that opened it; ``end()`` raises where a bridged span does not.

Design constraints (the update path must stay within 2% of the
uninstrumented baseline, and the *disabled* path must allocate
nothing):

- A disabled recorder's ``counter_add`` / ``event`` return before
  touching any container, and ``span()`` returns a shared no-op
  context-manager singleton. Hot call sites additionally guard with
  ``if rec.enabled:`` so keyword-argument dicts are never built on the
  disabled path.
- Finished spans are stored as flat tuples ``(name, track, t0, t1,
  parent, attrs)`` appended to one list — no per-span objects survive
  beyond their lifetime.
- A single lock guards the containers; it is only taken when enabled.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

# Canonical decomposition of destination stall time. Every benchmark
# reports these five components; they must (approximately) tile the
# end-to-end stall.
STALL_COMPONENTS = ("plan_wait", "wire", "decode", "verify", "control")

# Counter names the data planes feed and stall_breakdown() reads.
CTR_PLAN_WAIT = "stall/plan_wait"
CTR_WIRE = "stall/wire"  # gross time around transport calls
CTR_DECODE = "stall/decode"
CTR_VERIFY = "stall/verify"
CTR_CONTROL = "stall/control"

# Fused reshard decodes, one per destination unit, by the path that
# decoded it: the device kernel, or the NumPy fusion (kernel not
# requested, or the unit's frames are not kernel-shaped).
CTR_DECODE_KERNEL_UNITS = "decode/kernel_units"
CTR_DECODE_HOST_UNITS = "decode/host_units"

# Gray-failure self-healing counters (event counts, not seconds): each
# increment pairs with a span event of the same name carrying the
# source/unit involved.
CTR_RETRIES = "heal/retries"
CTR_HEDGES = "heal/hedges"
CTR_CORRUPT_REJECTS = "heal/corrupt_rejects"
CTR_DEADLINE_REPORTS = "heal/deadline_reports"

# Delta-transfer fallbacks: a delta frame met a stale/evicted base at
# the destination and the unit was transparently re-shipped through the
# base codec (event count; pairs with a "delta_stale_fallback" event).
CTR_DELTA_STALE = "heal/delta_stale_fallbacks"

# Networked data plane: reads served over a re-used keep-alive
# connection from the per-peer pool (event count; the complement of
# fresh TCP connects, which pay handshake + slow-start).
CTR_CONN_REUSE = "net/conn_reuses"


class _NullSpan:
    """Shared no-op span; returned by a disabled recorder."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def end(self) -> None:
        return None


NULL_SPAN = _NullSpan()

#: prefix of every program span's name on the profiler's host plane
PROFILER_PREFIX = "tensorhub."


class Span:
    """An open span. Close with ``end()`` or use as a context manager.

    Spans nest per ``track`` and thread: a span opened while another span
    opened by the same thread on the same track is open records that
    span's name as its ``parent``.
    """

    __slots__ = ("_rec", "name", "track", "thread", "t0", "parent", "attrs", "_ann")

    def __init__(self, rec: "Recorder", name: str, track: str, thread: int,
                 t0: float, parent: Optional[str], attrs: Optional[dict], ann):
        self._rec = rec
        self.name = name
        self.track = track
        self.thread = thread
        self.t0 = t0
        self.parent = parent
        self.attrs = attrs
        self._ann = ann

    def set(self, **attrs) -> "Span":
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def end(self) -> Optional[float]:
        """Close the span; returns its duration (``None`` if already closed)."""
        rec = self._rec
        if rec is None:
            return None
        if self._ann is not None and threading.get_ident() != self.thread:
            raise RuntimeError(
                f"span {self.name!r} on track {self.track!r} ended on another "
                "thread than the one that opened it"
            )
        self._rec = None
        t1 = rec._finish(self)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return t1 - self.t0

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class Recorder:
    """Collects spans, instant events and counters under an injected clock.

    Spans on the wall clock (``time.monotonic``) are bridged onto the
    JAX profiler (see the module docstring); spans on any other clock
    are not.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic, *,
                 enabled: bool = True):
        self.clock = clock
        self.enabled = enabled
        self._bridge = clock is time.monotonic
        # Finished spans: (name, track, t0, t1, parent, attrs-or-None).
        self.events: List[Tuple[str, str, float, float, Optional[str], Optional[dict]]] = []
        self.counters: Dict[str, float] = {}
        self._lock = threading.Lock()
        # Open-span stacks keyed by (track, thread) (parent attribution).
        self._open: Dict[Tuple[str, int], List[Span]] = {}
        # jax.profiler.TraceAnnotation, imported by the first bridged span
        self._annotation = None

    # -- spans ---------------------------------------------------------

    def span(self, name: str, track: str = "main", **attrs):
        """Open a span; returns a context manager with an ``end()``."""
        if not self.enabled:
            return NULL_SPAN
        thread = threading.get_ident()
        ann = None
        if self._bridge:
            cls = self._annotation
            if cls is None:
                from jax.profiler import TraceAnnotation as cls

                self._annotation = cls
            if cls.is_enabled():
                ann = cls(PROFILER_PREFIX + name, **attrs)
                ann.__enter__()
        t0 = self.clock()
        key = (track, thread)
        with self._lock:
            stack = self._open.get(key)
            parent = stack[-1].name if stack else None
            sp = Span(self, name, track, thread, t0, parent, attrs or None, ann)
            if stack is None:
                self._open[key] = [sp]
            else:
                stack.append(sp)
        return sp

    def _finish(self, sp: Span) -> float:
        t1 = self.clock()
        key = (sp.track, sp.thread)
        with self._lock:
            stack = self._open.get(key)
            if stack is not None and sp in stack:
                stack.remove(sp)
                if not stack:
                    del self._open[key]
            self.events.append((sp.name, sp.track, sp.t0, t1, sp.parent, sp.attrs))
        return t1

    def event(self, name: str, track: str = "main", **attrs) -> None:
        """Record an instantaneous (zero-duration) event."""
        if not self.enabled:
            return
        now = self.clock()
        with self._lock:
            stack = self._open.get((track, threading.get_ident()))
            parent = stack[-1].name if stack else None
            self.events.append((name, track, now, now, parent, attrs or None))

    # -- counters ------------------------------------------------------

    def counter_add(self, name: str, value: float = 1.0) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)

    # -- lifecycle -----------------------------------------------------

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.counters.clear()
            self._open.clear()


#: Shared disabled recorder, used as the default everywhere a recorder
#: is optional. Never enable this instance — create your own instead.
DISABLED = Recorder(enabled=False)


def wall_seconds(events: Iterable[tuple], names: Iterable[str], lo: float, hi: float) -> float:
    """Wall time covered by the named spans, clipped to ``[lo, hi]``: the
    length of the union of their intervals, so spans that overlap (threads
    working at once) count once. Where counters add thread-seconds, this
    reads the seconds a caller waited."""
    wanted = set(names)
    spans = sorted(
        (max(t0, lo), min(t1, hi)) for name, _, t0, t1, _, _ in events if name in wanted
    )
    total = 0.0
    cur_lo = cur_hi = None
    for s, e in spans:
        if e <= s:
            continue
        if cur_hi is not None and s <= cur_hi:
            cur_hi = max(cur_hi, e)
            continue
        if cur_hi is not None:
            total += cur_hi - cur_lo
        cur_lo, cur_hi = s, e
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def stall_breakdown(recorder: Recorder) -> Dict[str, float]:
    """Destination stall decomposition from a recorder's counters.

    ``stall/wire`` is gross time around transport calls; decode and
    checksum-verify time measured inside the transport is carved out
    of it so the five components tile rather than double-count.
    """
    c = recorder.counters
    decode = c.get(CTR_DECODE, 0.0)
    verify = c.get(CTR_VERIFY, 0.0)
    gross = c.get(CTR_WIRE, 0.0)
    return {
        "plan_wait": c.get(CTR_PLAN_WAIT, 0.0),
        "wire": max(0.0, gross - decode - verify),
        "decode": decode,
        "verify": verify,
        "control": c.get(CTR_CONTROL, 0.0),
    }
