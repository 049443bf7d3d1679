"""Transfer telemetry plane (observability).

``telemetry`` — the low-overhead recorder (spans / instant events /
counters), clock-injected so the same instrumentation runs under
``time.monotonic`` (threaded data plane) and ``SimEnv`` virtual time
(simulator); on the wall clock its spans also reach the JAX profiler's
trace as ``tensorhub.<name>``, and ``wall_seconds`` reads the wall time
a set of spans covers. ``export`` — Chrome trace-event JSON (Perfetto-viewable)
and a textual timeline renderer.
"""

from repro.obs.telemetry import (
    DISABLED,
    STALL_COMPONENTS,
    Recorder,
    stall_breakdown,
    wall_seconds,
)
from repro.obs.export import (
    chrome_trace_events,
    render_timeline,
    write_chrome_trace,
)
from repro.obs.rpc import RpcStats

__all__ = [
    "DISABLED",
    "STALL_COMPONENTS",
    "Recorder",
    "RpcStats",
    "chrome_trace_events",
    "render_timeline",
    "stall_breakdown",
    "wall_seconds",
    "write_chrome_trace",
]
