"""Multi-device distribution tests, run in subprocesses.

XLA locks the host device count at first jax init, so these spawn fresh
interpreters with ``--xla_force_host_platform_device_count`` set — the
same mechanism the dry-run uses, validated here at 8 devices where real
numeric comparison is cheap.
"""

import pytest

from procs import run_py as _run_py


def run_py(code: str, devices: int = 8) -> str:
    # shared harness: deadline from $REPRO_PROC_DEADLINE (default 420s)
    # with stdout/stderr tail dumps on both failure and timeout
    return _run_py(code, devices=devices)


@pytest.mark.timeout(500)
def test_shardmap_moe_matches_oracle_on_8_devices():
    out = run_py(
        """
        import dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        Auto = AxisType.Auto
        from repro.configs import get_config
        from repro.models import blocks, build_model, optim

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(Auto, Auto))
        cfg = get_config("dbrx-132b").reduced()
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        layer = jax.tree.map(lambda a: a[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 12, cfg.d_model))
        y_ref = blocks.moe_dense_ref(cfg, layer["ffn"], x)
        with mesh, optim.optimizations(mesh=mesh, shardmap_moe=True):
            y = jax.jit(lambda p, xx: blocks.moe_apply_shardmap(cfg, p, xx))(layer["ffn"], x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-5, err
        print("SHARDMAP_OK", err)
        """
    )
    assert "SHARDMAP_OK" in out


@pytest.mark.timeout(500)
def test_train_step_numerics_invariant_to_sharding():
    """One train step on a 2x4 mesh equals the single-device step."""
    out = run_py(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        Auto = AxisType.Auto
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.configs import get_config
        from repro.models import abstract_tree, build_model
        from repro.sharding import TRAIN_RULES, tree_shardings
        from repro.training import AdamW, make_train_step

        cfg = get_config("llama3-8b").reduced()
        model = build_model(cfg)
        opt = AdamW(lr=1e-3, weight_decay=0.0)
        step = make_train_step(model, cfg, opt)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        state = opt.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)

        p1, _, m1 = jax.jit(step)(params, state, {"tokens": toks})

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(Auto, Auto))
        psh = tree_shardings(model.param_specs(), TRAIN_RULES, mesh)
        with mesh:
            p2, _, m2 = jax.jit(step, in_shardings=(psh, None, None))(
                params, state, {"tokens": toks})
        # cross-device reduction reassociation (sharded-vocab softmax, grad
        # all-reduce) + AdamW's rsqrt amplification -> compare to ~1e-3
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
        worst = 0.0
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            worst = max(worst, float(jnp.max(jnp.abs(a - b))))
        assert worst < 5e-3, f"max param divergence {worst}"
        print("SHARDED_STEP_OK", worst)
        """
    )
    assert "SHARDED_STEP_OK" in out


@pytest.mark.timeout(500)
def test_h1_constraint_preserves_numerics():
    out = run_py(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        Auto = AxisType.Auto
        import numpy as np
        from repro.configs import get_config
        from repro.models import build_model, optim

        cfg = get_config("llama3-8b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
        base = model.forward(params, {"tokens": toks})
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(Auto, Auto))
        with mesh, optim.optimizations(mesh=mesh, shard_attn_heads=True):
            opt_out = jax.jit(lambda p, t: model.forward(p, {"tokens": t}))(params, toks)
        np.testing.assert_allclose(np.asarray(base), np.asarray(opt_out), rtol=2e-4, atol=2e-4)
        print("H1_NUMERICS_OK")
        """
    )
    assert "H1_NUMERICS_OK" in out
