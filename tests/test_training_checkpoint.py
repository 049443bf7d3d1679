"""Optimizer, objectives, grad accumulation, checkpoint/restart, sharding
rules, and the HLO analyzer."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec

from repro import checkpoint as ckpt
from repro.configs import get_config
from repro.data.synthetic import BigramStream, PromptSet
from repro.models import build_model
from repro.sharding import SERVE_RULES, TRAIN_RULES, spec_for
from repro.training import (
    AdamW,
    cosine_schedule,
    group_relative_advantages,
    grpo_loss,
    lm_cross_entropy,
    make_train_step,
)


class TestAdamW:
    def test_converges_on_quadratic(self):
        opt = AdamW(lr=0.1, weight_decay=0.0, grad_clip=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}  # d/dw w^2
            params, state = opt.update(grads, state, params)
        assert float(jnp.max(jnp.abs(params["w"]))) < 0.05

    def test_bf16_state_option(self):
        opt = AdamW(state_dtype=jnp.bfloat16)
        params = {"w": jnp.ones((4, 4))}
        state = opt.init(params)
        assert state.mu["w"].dtype == jnp.bfloat16
        p2, s2 = opt.update({"w": jnp.ones((4, 4))}, state, params)
        assert s2.mu["w"].dtype == jnp.bfloat16

    def test_grad_clip(self):
        opt = AdamW(lr=1.0, grad_clip=1.0, weight_decay=0.0)
        params = {"w": jnp.zeros(3)}
        state = opt.init(params)
        p2, _ = opt.update({"w": jnp.asarray([1e6, 0.0, 0.0])}, state, params)
        assert float(jnp.max(jnp.abs(p2["w"]))) < 1.1  # clipped step

    def test_schedule(self):
        sched = cosine_schedule(warmup=10, total=100)
        assert float(sched(jnp.asarray(0))) == 0.0
        assert math.isclose(float(sched(jnp.asarray(10))), 1.0, rel_tol=1e-5)
        assert float(sched(jnp.asarray(100))) < 1e-5


class TestObjectives:
    def test_lm_ce_perfect_prediction(self):
        toks = jnp.asarray([[1, 2, 3, 1]])
        logits = jax.nn.one_hot(jnp.asarray([[2, 3, 1, 0]]), 5) * 100.0
        loss, m = lm_cross_entropy(logits, toks)
        assert float(loss) < 1e-3 and float(m["accuracy"]) == 1.0

    def test_grpo_direction(self):
        """Positive advantage pushes sampled-token logprob up."""
        vocab, b, s = 7, 4, 6
        toks = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, vocab)
        logits = jnp.zeros((b, s, vocab))
        blp = jnp.full((b, s - 1), -jnp.log(vocab))
        adv = jnp.asarray([1.0, 1.0, -1.0, -1.0])
        mask = jnp.ones((b, s - 1), bool)

        def loss_fn(lg):
            return grpo_loss(lg, toks, blp, adv, mask)[0]

        g = jax.grad(loss_fn)(logits)
        tok_grad = jnp.take_along_axis(g[:, :-1], toks[:, 1:][..., None], axis=-1)[..., 0]
        # gradient descent increases logits where advantage > 0
        assert float(tok_grad[0].sum()) < 0 and float(tok_grad[2].sum()) > 0

    def test_group_advantages_zero_mean(self):
        r = jnp.asarray([1.0, 0.0, 3.0, 2.0])
        adv = group_relative_advantages(r, group_size=2)
        np.testing.assert_allclose(np.asarray(adv.reshape(2, 2).mean(1)), 0.0, atol=1e-6)


class TestGradAccumulation:
    def test_accum_matches_full_batch(self):
        cfg = get_config("llama3-8b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        opt = AdamW(lr=1e-2, weight_decay=0.0, grad_clip=0.0)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, cfg.vocab)
        step1 = jax.jit(make_train_step(model, cfg, opt, accum=1))
        step2 = jax.jit(make_train_step(model, cfg, opt, accum=2))
        p1, _, _ = step1(params, opt.init(params), {"tokens": toks})
        p2, _, _ = step2(params, opt.init(params), {"tokens": toks})
        # accumulation order differs -> tolerate float reassociation noise
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


class TestLossGoesDown:
    def test_bigram_learnable(self):
        cfg = get_config("llama3-8b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        opt = AdamW(lr=3e-3, weight_decay=0.0)
        step = jax.jit(make_train_step(model, cfg, opt))
        state = opt.init(params)
        stream = BigramStream(vocab=cfg.vocab, seq_len=32, batch=8, seed=0, branching=2)
        losses = []
        for _ in range(30):
            batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
            params, state, m = step(params, state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.75, losses


class TestCheckpoint:
    def test_roundtrip_and_latest(self, tmp_path):
        tree = {"a": jnp.arange(10, dtype=jnp.float32), "b": [jnp.ones((3, 3)), jnp.zeros(2)]}
        ckpt.save(str(tmp_path), 5, tree, metadata={"stream_offset": 42})
        ckpt.save(str(tmp_path), 9, jax.tree.map(lambda x: x + 1, tree))
        assert ckpt.latest_step(str(tmp_path)) == 9
        restored, step, meta = ckpt.restore(str(tmp_path), tree, step=5)
        assert step == 5 and meta["stream_offset"] == 42
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(10, dtype=np.float32))

    def test_no_partial_checkpoint_visible(self, tmp_path):
        tree = {"w": jnp.ones(4)}
        ckpt.save(str(tmp_path), 1, tree)
        # a stale tmp dir from a crashed save must not affect LATEST
        os.makedirs(tmp_path / "step_00000002.tmp")
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_stream_resumes_deterministically(self):
        s1 = BigramStream(vocab=64, seq_len=8, batch=2, seed=3)
        batches = [s1.next_batch()["tokens"] for _ in range(5)]
        s2 = BigramStream(vocab=64, seq_len=8, batch=2, seed=3, offset=3)
        np.testing.assert_array_equal(s2.next_batch()["tokens"], batches[3])

    def test_prompt_reward_range(self):
        ps = PromptSet(vocab=64, prompt_len=4, seed=0)
        seqs = ps.sample(6, step=0)
        full = np.concatenate([seqs, seqs[:, -1:]], axis=1)
        r = ps.reward(full, prompt_len=4)
        assert r.shape == (6,) and np.all((0 <= r) & (r <= 1))


class TestShardingRules:
    MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))

    def test_divisibility_fallback(self):
        # gemma2: 4 kv heads cannot shard 16 ways -> replicated
        spec = spec_for((4, 32, 256), ("kv_heads", None, "head_dim"), TRAIN_RULES, self.MESH)
        assert spec == PartitionSpec(None, None, "model")

    def test_first_fit_conflict(self):
        # [experts, embed, expert_mlp]: experts takes model; expert_mlp skipped
        spec = spec_for((16, 7168, 2048), ("experts", "embed", "expert_mlp"), TRAIN_RULES, self.MESH)
        assert spec == PartitionSpec("model", ("pod", "data"), None)

    def test_serve_ep_over_two_axes(self):
        spec = spec_for((256, 7168, 2048), ("experts", "embed", "expert_mlp"), SERVE_RULES, self.MESH)
        assert spec == PartitionSpec(("data", "model"), None, None)

    def test_single_pod_mesh_drops_pod_axis(self):
        mesh = AbstractMesh((16, 16), ("data", "model"))
        spec = spec_for((256, 4096), ("batch", None), TRAIN_RULES, mesh)
        assert spec == PartitionSpec("data", None)


class TestHloAnalyzer:
    def test_scan_trip_count_multiplies_flops(self):
        from repro.launch.hlo_analyzer import analyze

        k = jnp.ones((64, 64), jnp.float32)

        def f(x):
            def body(c, _):
                return c @ k, None

            out, _ = jax.lax.scan(body, x, None, length=17)
            return out

        compiled = jax.jit(f).lower(jnp.ones((64, 64))).compile()
        costs = analyze(compiled.as_text())
        expected = 17 * 2 * 64 * 64 * 64
        assert abs(costs.dot_flops - expected) / expected < 0.01

    def test_collective_parse(self):
        from repro.launch.hlo_analyzer import analyze

        hlo = """
HloModule test

ENTRY %main (p: f32[16,8]) -> f32[16,8] {
  %p = f32[16,8]{1,0} parameter(0)
  %ag = f32[32,8]{1,0} all-gather(%p), channel_id=1, replica_groups={{0,1}}, dimensions={0}
  ROOT %ar = f32[16,8]{1,0} all-reduce(%p), channel_id=2, replica_groups={{0,1}}, to_apply=%add
}
"""
        costs = analyze(hlo)
        assert costs.collective_bytes["all-gather"] == 32 * 8 * 4
        assert costs.collective_bytes["all-reduce"] == 16 * 8 * 4
