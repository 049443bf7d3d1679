"""Each cell at tiny widths on the CPU, driven through ``run.main`` past
its look for a chip: the program's updates pass the reference comparison,
the control (the reference in a lower precision) fails it, and so does
each fault the timed path of a one-chip cell can have."""

import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import deployment as dep
import harness
import run
from repro.core.client import ShardHandle
from repro.transfer.engine import LocalTransport

#: published layouts, widths cut so a run takes seconds on the CPU; the
#: embedding shard stays above the 2 MiB tiny-tensor bucket size
TINY = dict(
    hidden_size=1024,
    intermediate_size=4096,
    num_attention_heads=8,
    num_key_value_heads=8,
    head_dim=128,
    vocab_size=16384,
    num_hidden_layers=1,
)
#: the benchmark's cells
CELLS = {
    "yi34b-raw-update": ("yi-34b-tp8", "same-dc-update"),
    "dscoder33b-reshard-update": ("deepseek-coder-33b-tp4-tp8", "reshard-update"),
}
SEED = 2**31 + 12345


@pytest.fixture
def tiny_run(monkeypatch, capsys):
    """``run.main`` past its look for a chip, at the tiny widths; returns
    the result line of a run."""
    real = dep.load_config
    monkeypatch.setattr(dep, "load_config", lambda name, root=dep.HERE: dict(real(name, root), **TINY))
    monkeypatch.setattr(run, "check_devices", lambda chips: jax.devices())
    monkeypatch.setattr(
        dep,
        "find_workload",
        lambda bench, name: dict(name=name, config=CELLS[name][0], traffic=CELLS[name][1], chips=1),
    )
    monkeypatch.setattr(dep, "load_peaks", lambda kind, root=dep.HERE: {"hbm_bytes_per_s": 819e9})
    # the CPU backend keeps no memory statistics
    monkeypatch.setattr(harness.Cell, "peak_hbm", lambda self: 1)

    def go(workload, *extra):
        capsys.readouterr()
        run.main(["--workload", workload, "--seed", str(SEED), "--seconds", "0.3", *extra])
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go


@pytest.mark.parametrize("workload", CELLS)
def test_program_passes_and_control_fails(workload, tiny_run):
    out = tiny_run(workload, "--trace", "0", "--control", "1")
    assert out["correct"] is True
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())
    assert out["control"]["digest_mismatches"]["value"] > 0
    assert out["control"]["final_mismatched_elements"]["value"] > 0
    assert set(out["metrics"]) == {"weight_sync_s", "hbm_peak_GB", "setup_s"}
    assert out["window_compiles"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_per_layer_metrics(workload, tiny_run):
    out = tiny_run(workload, "--trace", "1")
    assert out["correct"] is True
    assert {"publish_s", "pull_s", "land_s", "verify_thread_s"} <= set(out["metrics"])


def _unchanged(monkeypatch, fired):
    """An update that reports success and leaves the rollout's bytes as they were."""
    real = ShardHandle.update

    def update(self, version="latest"):
        if self.replica.startswith("rollout"):
            self.current_version = max(self.client.server.list_versions(self.model))
            fired.append(self.current_version)
            return True
        return real(self, version)

    monkeypatch.setattr(ShardHandle, "update", update)


def _half_landed(monkeypatch, fired):
    """Landing that leaves every second tensor of an update behind."""
    real = harness.land_rollout

    def land(handle, device, landed):
        if not landed:
            return real(handle, device, landed)
        names = list(handle.store.tensors())
        half = {n: handle.store.get(n) for n in names[::2]}
        fired.append(len(names) - len(half))
        return real(SimpleNamespace(store=SimpleNamespace(tensors=lambda: half)), device, landed)

    monkeypatch.setattr(harness, "land_rollout", land)


def _altered(monkeypatch, done):
    """One element of one pulled unit altered where the transport writes it."""
    real_pull = LocalTransport.pull_unit
    real_range = LocalTransport.read_unit_range

    def pull_unit(self, src, shard, unit, expected, dst, *a, **kw):
        real_pull(self, src, shard, unit, expected, dst, *a, **kw)
        if not done and dst.worker_id.startswith("rollout"):
            name = unit.members[0] if unit.members else unit.name
            dst.get(name).view(np.uint16).reshape(-1)[0] ^= 1
            done.append(name)

    def read_unit_range(self, *a, **kw):
        out = real_range(self, *a, **kw)
        if not done and out.size >= 2:
            out = out.copy()
            out.view(np.uint8)[1] ^= 0x40
            done.append("range")
        return out

    monkeypatch.setattr(LocalTransport, "pull_unit", pull_unit)
    monkeypatch.setattr(LocalTransport, "read_unit_range", read_unit_range)


FAULTS = {"state_unchanged": _unchanged, "half_left_out": _half_landed, "answer_altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_fails_the_check(workload, fault, tiny_run, monkeypatch):
    fired = []
    FAULTS[fault](monkeypatch, fired)
    out = tiny_run(workload, "--trace", "0")
    assert fired
    assert out["correct"] is False
