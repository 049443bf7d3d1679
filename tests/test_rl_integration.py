"""Integration: the full RL loop (Fig 4) across trainer + rollout threads
with real weight bytes moving through TensorHub — plus the swarm-pull
strong-consistency scenario (trainer rolls v+1 while rollouts are mid-
swarm-pull of v; no rollout may ever observe a torn version)."""

import threading
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ReferenceServer, TensorHubClient
from repro.data.synthetic import PromptSet
from repro.rl import RLConfig, RolloutWorker, TrainerWorker


@pytest.mark.timeout(300)
def test_rl_loop_end_to_end():
    model_cfg = get_config("llama3-8b").reduced()
    cfg = RLConfig(num_steps=3, prompt_len=6, response_len=8, num_prompts=2, group_size=2)
    server = ReferenceServer()
    hub = TensorHubClient(server)
    prompts = PromptSet(vocab=model_cfg.vocab, prompt_len=cfg.prompt_len)
    queue, stop = [], threading.Event()
    trainer = TrainerWorker(hub, cfg, model_cfg, queue)
    workers = [
        RolloutWorker(f"rollout-{i}", hub, cfg, model_cfg, prompts, queue, stop)
        for i in range(2)
    ]
    for w in workers:
        w.start()
    try:
        for step in range(cfg.num_steps):
            deadline = time.monotonic() + 240
            while len(queue) < 2:
                for w in workers:
                    if w.error:
                        raise w.error
                assert time.monotonic() < deadline, "rollouts stalled"
                time.sleep(0.05)
            m = trainer.train_on([queue.pop(0), queue.pop(0)])
            assert m["version"] == step + 1
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=90)
    for w in workers:
        if w.error:
            raise w.error
    trainer.close()
    # every published version was replicated at least once; no corruption
    assert server.stats["publishes"] >= cfg.num_steps
    assert server.stats["replications_completed"] >= 2
    # rollouts converged to a recent version
    assert all(w.weights_version is not None and w.weights_version >= 1 for w in workers)


# ---------------------------------------------------------------------------
# swarm pull vs. concurrent publish: strong consistency (Table 2 semantics)
# ---------------------------------------------------------------------------


def _weights(version: int):
    """Deterministic per-version weights, distinguishable byte-for-byte."""
    rng = np.random.default_rng(1000 + version)
    return {
        "wq": rng.integers(0, 255, size=(128, 512), dtype=np.uint8),
        "wk": np.full((64, 64), float(version), dtype=np.float32),
        "scale": np.full((8,), 0.5 + version, dtype=np.float32),
    }


def _expect_version(handle, version: int) -> None:
    want = _weights(version)
    for name, arr in want.items():
        got = handle.store.get(name)
        assert np.array_equal(got, arr), (
            f"{handle.replica}: tensor {name} is not pure v{version} "
            "(torn or stale bytes observed)"
        )


@pytest.mark.timeout(300)
def test_publish_next_version_during_swarm_pull_no_torn_reads():
    """Fig 4 steady state under swarm replication: rollouts are mid-swarm-
    pull of v1 (several concurrent readers, each other's prefixes in the
    availability map) while the trainer unpublishes v1 and publishes v2.

    Strong consistency requires: (a) every rollout's replicate(v1) lands
    pure v1 bytes — the retention drain means the trainer cannot mutate
    buffers readers still pull from; (b) a subsequent update("latest")
    lands pure v2; (c) no interleaving ever shows a mix of the two."""
    server = ReferenceServer()
    hub = TensorHubClient(server, window=3, chunk_bytes=8192)

    trainer = hub.open("rl", "trainer", 1, 0)
    trainer.register(_weights(1))
    trainer.publish(1)
    # a second full copy so rollout pulls multi-source from the start
    mirror = hub.open("rl", "mirror", 1, 0)
    mirror.register(_weights(0))
    mirror.replicate(1)

    rollouts = [hub.open("rl", f"rollout-{i}", 1, 0) for i in range(3)]
    for i, r in enumerate(rollouts):
        r.register(_weights(0))

    pulled = threading.Barrier(len(rollouts) + 1, timeout=60)
    errs = []

    def pull(h):
        try:
            v = h.replicate(1)
            assert v == 1
            _expect_version(h, 1)  # pure v1: no v2 bytes leaked mid-pull
            pulled.wait()
        except BaseException as e:  # noqa: BLE001
            errs.append((h.replica, e))
            try:
                pulled.wait()
            except threading.BrokenBarrierError:
                pass

    threads = [threading.Thread(target=pull, args=(r,)) for r in rollouts]
    for t in threads:
        t.start()

    # trainer rolls the version while the swarm pull is in flight: the
    # unpublish drains (readers hold refcounts) before buffers may mutate
    trainer.unpublish()
    for name, arr in _weights(2).items():
        trainer.store.get(name)[...] = arr  # legal only after drain
    trainer.publish(2)
    pulled.wait()
    for t in threads:
        t.join(timeout=60)
    assert not errs, f"rollout errors: {errs}"

    # every rollout flips to v2 atomically via update("latest")
    for r in rollouts:
        assert r.update("latest") is True
        assert r.current_version == 2
        _expect_version(r, 2)
    for r in rollouts + [mirror, trainer]:
        r.close()


@pytest.mark.timeout(300)
def test_rollout_lands_weights_on_its_device():
    """A rollout given a device lands pulled weights there, not on JAX's
    default device (two virtual CPU devices in a fresh interpreter)."""
    from procs import run_py

    out = run_py(
        """
        import threading
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import get_config
        from repro.core import ReferenceServer, TensorHubClient
        from repro.data.synthetic import PromptSet
        from repro.models import named_tensors
        from repro.rl import RLConfig, RolloutWorker

        cfg = get_config("llama3-8b").reduced()
        target = jax.devices()[1]
        w = RolloutWorker(
            "r", TensorHubClient(ReferenceServer()), RLConfig(), cfg,
            PromptSet(vocab=cfg.vocab, prompt_len=8), [], threading.Event(),
            device=target,
        )
        params = w.model.init(jax.random.PRNGKey(0), jnp.float32)
        bufs = {k: np.asarray(v) for k, v in named_tensors(params).items()}
        landed = w._params_from_buffers(params, bufs)
        on = {d for leaf in jax.tree.leaves(landed) for d in leaf.devices()}
        assert on == {target}, on
        print("LANDED_ON", target.id)
        """,
        devices=2,
    )
    assert "LANDED_ON 1" in out
