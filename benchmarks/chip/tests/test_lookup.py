"""Names are looked up in data: a configuration, a traffic mix and a
per-layer metric reader dropped in as new files are found by name; an
unknown device kind is an error; with no TPU a run exits non-zero and
prints no result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import deployment as dep
import run

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]


def test_new_files_are_found_by_name(tmp_path):
    for sub in ("configs", "mixes", "layer_metrics"):
        (tmp_path / sub).mkdir()
    cfg = json.loads((CHIP / "configs" / "yi-34b-tp8.json").read_text())
    cfg["name"] = "new-model"
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((CHIP / "mixes" / "same-dc-update.json").read_text())
    mix["name"] = "new-mix"
    (tmp_path / "mixes" / "new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "layer_metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return len(ctx['updates']), 'count'\n"
    )
    assert dep.load_config("new-model", tmp_path)["hidden_size"] == 7168
    assert dep.load_mix("new-mix", tmp_path)["replicas"] == 1
    assert run.load_reader("new_metric", tmp_path)({"updates": [1, 2]}) == (2, "count")
    with pytest.raises(FileNotFoundError):
        dep.load_config("absent", tmp_path)


def test_every_name_in_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        dep.load_config(w["config"])
        dep.load_mix(w["traffic"])
        assert dep.metrics_for(bench, w["name"], "per_layer")
    for m in bench["per_layer"]:
        assert callable(run.load_reader(m["name"]))


def test_metrics_for_honours_workload_lists():
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in dep.metrics_for(bench, "x", "per_layer")] == ["a", "b"]
    assert [m["name"] for m in dep.metrics_for(bench, "y", "per_layer")] == ["a"]


def test_unknown_device_kind_is_an_error():
    assert dep.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        dep.load_peaks("TPU v9 imaginary")


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "yi34b-raw-update",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr
