"""Elastic rollout on 'spot instances' (paper 5.3): rollout workers join
and get preempted mid-training; TensorHub keeps the cluster self-healing
with zero trainer involvement.

    PYTHONPATH=src python examples/elastic_rollout.py
"""

import threading
import time
import traceback

from repro.configs import get_config
from repro.core import ReferenceServer, TensorHubClient
from repro.data.synthetic import PromptSet
from repro.launch.compile_cache import enable_compile_cache
from repro.rl import RLConfig, RolloutWorker, TrainerWorker


def main() -> None:
    enable_compile_cache()
    model_cfg = get_config("llama3-8b").reduced()
    cfg = RLConfig(num_steps=6, prompt_len=6, response_len=10, num_prompts=2, group_size=2)
    server = ReferenceServer()
    hub = TensorHubClient(server)
    prompts = PromptSet(vocab=model_cfg.vocab, prompt_len=cfg.prompt_len)
    queue, stop = [], threading.Event()

    trainer = TrainerWorker(hub, cfg, model_cfg, queue)
    stable = RolloutWorker("standalone-0", hub, cfg, model_cfg, prompts, queue, stop)
    stable.start()

    spot_stop = threading.Event()
    spot = RolloutWorker(
        "elastic-0", hub, cfg, model_cfg, prompts, queue, spot_stop, is_spot=True
    )

    def check(workers):
        for w in workers:
            if w.error:
                traceback.print_exception(w.error)
                raise SystemExit(1)

    try:
        for step in range(cfg.num_steps):
            if step == 1:
                print(">>> scale-up: elastic-0 joins (pulls weights on demand)")
                spot.start()
            if step == 4:
                print(">>> preemption: elastic-0 killed without grace")
                spot_stop.set()
                hub.registry.fail_replica("elastic-0")
                server.fail_replica("m" if False else cfg.model_name, "elastic-0",
                                    reason="spot preemption")
            rollouts = trainer.wait_for_rollouts(1, timeout=300)
            check([stable])
            m = trainer.train_on(rollouts)
            live = sorted({r for rs in server.list_versions(cfg.model_name).values() for r in rs})
            print(f"step {step}: v{m['version']} reward {m['mean_reward']:.3f}  live replicas: {live}")
    finally:
        stop.set()
        spot_stop.set()
        stable.join(timeout=90)
        spot.join(timeout=10)
    trainer.close()
    print("stats:", server.stats)
    print(f"evictions handled: {server.stats['evictions']} (training never stopped)")


if __name__ == "__main__":
    main()
