"""The networked demo's spawner starts every child off the accelerator."""

from repro.launch import networked


def test_children_start_with_jax_on_the_cpu(monkeypatch, tmp_path):
    envs = []

    class FakeProc:
        pid = 0

        def __init__(self, argv, env=None):
            envs.append(env)

        def wait(self):
            return 0

        def poll(self):
            return 0

        def terminate(self):
            pass

        def kill(self):
            pass

    monkeypatch.setattr(networked.subprocess, "Popen", FakeProc)
    monkeypatch.setattr(networked.time, "sleep", lambda s: None)
    assert networked.main(["--workers", "3", "--run-dir", str(tmp_path)]) == 0
    # controller + three workers, each with JAX held to the CPU
    assert len(envs) == 4
    assert all(env["JAX_PLATFORMS"] == "cpu" for env in envs)
