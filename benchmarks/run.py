"""Benchmark runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json out.json]

Prints, per benchmark, a ``name,metric,value`` CSV block followed by the
claim-validation lines (paper number vs measured). ``--json`` also
writes the machine-readable aggregate — a list of per-benchmark dicts
(``harness.result_dict``: name, rows, checks, mismatches, elapsed_s) —
which the CI ``--quick`` job uploads as an artifact. ``--quick`` runs
each module's reduced grid and makes errors/mismatches fail the exit
code (the same contract as each module's own ``--quick`` CLI).
"""

from __future__ import annotations

import inspect
import sys
import time
import traceback

from benchmarks import (
    chaos,
    cross_dc,
    elastic,
    failover,
    fanout,
    harness,
    micro_bandwidth,
    micro_burst,
    micro_failure,
    perf_transfer,
    reshard,
    roofline,
    standalone,
    swarm,
)

MODULES = [
    ("fig7a_bandwidth", micro_bandwidth),
    ("fig7b_burst", micro_burst),
    ("fig7c_failure", micro_failure),
    ("chaos_sweep", chaos),
    ("fanout_scheduler", fanout),
    ("swarm_replication", swarm),
    ("failover_control_plane", failover),
    ("fig9_standalone", standalone),
    ("fig11_elastic", elastic),
    ("fig12_cross_dc", cross_dc),
    ("reshard_codec", reshard),
    ("perf_transfer_iterations", perf_transfer),
    ("roofline_table", roofline),
]


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in args
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        if i + 1 >= len(args):
            raise SystemExit("--json requires a path argument")
        json_path = args[i + 1]
    failures = 0
    mismatches = 0
    results = []
    for name, mod in MODULES:
        print(f"\n=== {name} ===")
        t0 = time.time()
        try:
            # the micro/roofline modules have no reduced grid to select
            takes_quick = "quick" in inspect.signature(mod.run).parameters
            rows = mod.run(quick=True) if quick and takes_quick else mod.run()
            checks = mod.validate(rows)
        except Exception:  # noqa: BLE001 - keep running remaining figures
            traceback.print_exc()
            failures += 1
            results.append(
                {
                    "name": name,
                    "error": traceback.format_exc(limit=3),
                    "elapsed_s": round(time.time() - t0, 2),
                }
            )
            continue
        for r in rows:
            items = ",".join(f"{k}={v}" for k, v in r.items())
            print(f"{name},{items}")
        for c in checks:
            print(f"  [check] {c}")
            if "MISMATCH" in c:
                mismatches += 1
        print(f"  ({time.time()-t0:.1f}s)")
        results.append(harness.result_dict(name, rows, checks, time.time() - t0))
    print(f"\nsummary: {len(MODULES)} benchmarks, {failures} errors, {mismatches} claim mismatches")
    if json_path:
        harness.write_json(json_path, results)
        print(f"wrote {json_path}")
    return 1 if failures or (quick and mismatches) else 0


if __name__ == "__main__":
    raise SystemExit(main())
