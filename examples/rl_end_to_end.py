"""End-to-end RL driver: GRPO training of a small LM with weight transfer
through TensorHub (the paper's full loop: generate -> score -> train ->
transfer).

    PYTHONPATH=src python examples/rl_end_to_end.py --steps 30
    PYTHONPATH=src python examples/rl_end_to_end.py --steps 300 --d-model 256 \
        --layers 8   # ~100M-scale run (slow on CPU)

The reward is rule-based (valid bigram-chain continuations); mean reward
rises as the policy learns the chain. Weight versions flow trainer ->
rollouts via publish/update; the server stats at the end show the
reference traffic. Rollout workers take JAX's devices in turn, and each
lands its pulled weights on its own.
"""

import argparse
import dataclasses
import threading
import time
import traceback

import jax

from repro.configs import get_config
from repro.core import ReferenceServer, TensorHubClient
from repro.data.synthetic import PromptSet
from repro.launch.compile_cache import enable_compile_cache
from repro.rl import RLConfig, RolloutWorker, TrainerWorker


def _raise_worker_errors(workers) -> None:
    for w in workers:
        if w.error:
            traceback.print_exception(w.error)
            raise SystemExit(1)


def main(argv=None) -> list:
    """Runs the loop; returns the trainer's per-step metrics."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--rollout-workers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=0, help="override width (0 = reduced config)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    model_cfg = dataclasses.replace(get_config("llama3-8b").reduced(), vocab=128)
    if args.d_model:
        model_cfg = dataclasses.replace(
            model_cfg,
            d_model=args.d_model,
            num_layers=args.layers or model_cfg.num_layers,
            vocab=args.vocab or 2048,
            d_ff=args.d_model * 4,
        )
    cfg = RLConfig(
        num_steps=args.steps, prompt_len=8, response_len=12,
        num_prompts=2, group_size=8, lr=args.lr,
        checkpoint_dir=args.ckpt_dir,
    )

    server = ReferenceServer()
    hub = TensorHubClient(server)
    prompts = PromptSet(vocab=model_cfg.vocab, prompt_len=cfg.prompt_len, branching=2)
    queue, stop = [], threading.Event()

    trainer = TrainerWorker(hub, cfg, model_cfg, queue)
    devices = jax.devices()
    workers = [
        RolloutWorker(
            f"rollout-{i}", hub, cfg, model_cfg, prompts, queue, stop,
            device=devices[i % len(devices)],
        )
        for i in range(args.rollout_workers)
    ]
    for w in workers:
        w.start()

    t0 = time.time()
    try:
        for step in range(cfg.num_steps):
            rollouts = trainer.wait_for_rollouts(args.rollout_workers, timeout=600)
            _raise_worker_errors(workers)
            m = trainer.train_on(rollouts)
            if step % 5 == 0 or step == cfg.num_steps - 1:
                print(
                    f"step {step:4d}  reward {m['mean_reward']:.3f}  "
                    f"loss {m['loss']:+.4f}  version {m['version']}  "
                    f"({time.time()-t0:.0f}s)"
                )
            if args.ckpt_dir and (step + 1) % 20 == 0:
                from repro import checkpoint as ckpt_lib

                ckpt_lib.save(args.ckpt_dir, step + 1, (trainer.params, trainer.opt_state))
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=120)
    _raise_worker_errors(workers)
    trainer.close()

    first = trainer.metrics_log[0]["mean_reward"]
    last10 = trainer.metrics_log[-10:]
    avg_last = sum(m["mean_reward"] for m in last10) / len(last10)
    print(f"\nreward: first {first:.3f} -> last-10 avg {avg_last:.3f}")
    print("server stats:", server.stats)
    print("rollout steps:", {w.name: w.steps_done for w in workers})
    return trainer.metrics_log


if __name__ == "__main__":
    main()
