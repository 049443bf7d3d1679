#!/usr/bin/env python3
"""Benchmark of TensorHub's weight sync on the chip: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every name is looked up in data: the cell in ``BENCHMARK.json`` at the
checkout's root, its configuration in ``configs/<config>.json``, its
traffic in ``mixes/<traffic>.json``, each per-layer metric's reader in
``layer_metrics/<metric>.py`` and the device's peaks in ``peaks.json``.

Set-up makes version 0 from the seed, publishes it, pulls and lands it on
every rollout chip (which compiles every program the window uses), then
runs the mix's ``warm_updates`` untimed updates, past the first updates
of a run, which are slower. The window runs updates for ``--seconds``.
After it, the landed bytes are compared with the bytes ``gen`` makes
again from the seed. With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window and from the program's counters.
``--control 1`` also prints the readings of the control: the reference in
a lower precision, put in the program's place (not part of a run).

Standard output ends with one JSON line; standard error ends with each
number compared beside its limit. With no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import deployment as dep  # noqa: E402

#: host spans the harness writes into the trace
SPANS = ("window", "publish", "rewrite", "pull", "land", "digest")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_reader(name: str, root: Path = HERE):
    """The ``read(ctx)`` function of ``layer_metrics/<name>.py``."""
    path = root / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_devices(chips: int):
    """The chips of this machine; exits 2 where JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"run.py: needs a TPU, JAX found {devs[0].platform}")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"run.py: the cell needs {chips} chips, JAX found {len(devs)}")
        raise SystemExit(2)
    return devs


class CompileCounter:
    """Counts programs traced or compiled while ``active``."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def host_mem_used() -> int:
    """``MemTotal - MemAvailable``: a process's RSS on a TPU host counts
    device mappings."""
    with open("/proc/meminfo") as f:
        m = {k: int(v.split()[0]) * 1024 for k, v in (line.split(":", 1) for line in f)}
    return m["MemTotal"] - m["MemAvailable"]


def mean(xs):
    return sum(xs) / len(xs)


def end_to_end(updates, setup_s: float, peak: int) -> dict:
    return {
        "setup_s": (setup_s, "s"),
        "weight_sync_s": (mean([u["sync_s"] for u in updates]), "s"),
        "hbm_peak_GB": (peak / 1e9, "GB"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    work = dep.find_workload(bench, args.workload)
    devs = check_devices(int(work["chips"]))
    kind = devs[0].device_kind
    peaks = dep.load_peaks(kind)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program of a run goes to the cache, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device {kind} x{len(devs)}, compile cache {cache}")

    import harness

    cfg = dep.load_config(work["config"])
    mix = dep.load_mix(work["traffic"])
    e2e = [m["name"] for m in dep.metrics_for(bench, args.workload, "end_to_end")]
    layer = [m["name"] for m in dep.metrics_for(bench, args.workload, "per_layer")]
    readers = {name: load_reader(name) for name in layer} if args.trace else {}

    recorder = None
    if args.trace:
        from repro import obs

        recorder = obs.Recorder()
    cell = harness.Cell(cfg, mix, args.seed, devs, recorder=recorder, annotate=bool(args.trace))
    cell.start()
    for _ in range(int(mix["warm_updates"])):
        u = cell.update()
        log(f"set-up update v{u['version']}: sync {u['sync_s']:.4f} s")
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.4f} s")

    compiles = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="tensorhub-trace-") if args.trace else None
    compiles.active = True
    if args.trace:
        # the Python tracer would record every call of the host path; host
        # level 1 keeps the harness's spans and drops the runtime's own
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            updates = cell.window(args.seconds)
        jax.profiler.stop_trace()
    else:
        updates = cell.window(args.seconds)
    compiles.active = False
    for u in updates:
        log(
            f"update v{u['version']}: sync {u['sync_s']:.4f} s = publish {u['publish_s']:.4f}"
            f" + pull {u['pull_s']:.4f} + land {u['land_s']:.4f}; rewrite {u['rewrite_s']:.4f} s"
            " (not counted)"
        )
    log(f"window: {len(updates)} updates, {compiles.count} programs traced or compiled in it")
    log(f"host memory in use {host_mem_used() / 1e9:.2f} GB")
    peak = cell.peak_hbm()

    reduced = None
    if args.trace:
        import trace_reduce

        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir), SPANS)
        )
        shutil.rmtree(trace_dir, ignore_errors=True)

    cell.release()
    checks = harness.compare(cell)
    correct = all(v == 0 for v in checks.values())
    control = harness.compare(cell, control=True) if args.control else None

    if args.trace:
        ctx = {"cell": cell, "updates": updates, "trace": reduced, "peaks": peaks}
        metrics = {}
        for name, read in readers.items():
            got = read(ctx)
            if got is not None:
                metrics[name] = {"value": got[0], "unit": got[1]}
    else:
        values = end_to_end(updates, setup_s, peak)
        metrics = {n: {"value": values[n][0], "unit": values[n][1]} for n in e2e}

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs), "memory_peak_bytes": peak}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {
        "correct": correct,
        "attempted": len(updates) * len(cell.rollouts),
        "failed": 0,
        "metrics": metrics,
        "device": device,
        "window_updates": len(updates),
        "window_compiles": compiles.count,
    }
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    if control is not None:
        result["control"] = {k: {"value": v, "limit": 0} for k, v in control.items()}
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    if control is not None:
        log("control: " + "; ".join(f"{k} {v} (limit 0)" for k, v in control.items()))
    log("checks: " + "; ".join(f"{k} {v} (limit 0)" for k, v in checks.items()))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
