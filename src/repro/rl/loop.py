"""RL training loop wired through TensorHub (paper Fig. 4).

``TrainerWorker`` follows Fig. 4a (publish -> rollout elsewhere -> unpublish
-> train -> publish next); ``RolloutWorker`` follows Fig. 4b (replicate the
initial weights, then poll ``update("latest")`` between inference batches).
Weight transfer between them is the *real* control+data plane: the
ReferenceServer routes, the LocalTransport moves actual bytes between the
workers' registered buffers, checksums verify end to end.

Workers run as threads in one process — the same topology the paper's
integration test rig uses (4.6: single-process multi-client simulation);
on a real cluster each worker is a JAX process and nothing here changes.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TensorHubClient
from repro.core.errors import StaleHandleError, TensorHubError
from repro.data.synthetic import PromptSet
from repro.models import build_model, named_tensors
from repro.training import (
    AdamW,
    group_relative_advantages,
    make_grpo_step,
)


@dataclasses.dataclass
class RLConfig:
    model_name: str = "actor"
    num_steps: int = 20
    prompt_len: int = 8
    response_len: int = 24
    num_prompts: int = 4
    group_size: int = 4  # responses per prompt (GRPO group)
    lr: float = 1e-3
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 10


def sample_responses(
    model, params, prompts: jax.Array, response_len: int, key: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Autoregressive sampling; returns (sequences, per-token logprobs).

    Uses prefill + decode — the same serve path the big configs lower.
    """
    b, plen = prompts.shape
    total = plen + response_len
    logits, cache, cache_len = model.prefill(params, {"tokens": prompts}, max_len=total)

    def step(carry, k):
        cache, cache_len, logits, seq_pos, toks = carry
        lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        nxt = jax.random.categorical(k, lp, axis=-1)  # [B]
        chosen_lp = jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]
        toks = jax.lax.dynamic_update_slice(toks, nxt[:, None].astype(jnp.int32), (0, seq_pos))
        new_logits, cache = model.decode(params, cache, nxt[:, None].astype(jnp.int32), cache_len)
        return (cache, cache_len + 1, new_logits, seq_pos + 1, toks), chosen_lp

    toks0 = jnp.concatenate(
        [prompts.astype(jnp.int32), jnp.zeros((b, response_len), jnp.int32)], axis=1
    )
    keys = jax.random.split(key, response_len)
    (cache, _, _, _, toks), lps = jax.lax.scan(
        step, (cache, cache_len, logits, plen, toks0), keys
    )
    return toks, lps.T  # [B, total], [B, response_len]


class RolloutWorker(threading.Thread):
    """Fig. 4b: standalone rollout pulling weights on demand."""

    def __init__(
        self,
        name: str,
        hub: TensorHubClient,
        cfg: RLConfig,
        model_cfg,
        prompts: PromptSet,
        out_queue: List,
        stop: threading.Event,
        *,
        datacenter: str = "dc0",
        is_spot: bool = False,
        device: Optional[jax.Device] = None,
    ) -> None:
        super().__init__(name=name, daemon=True)
        self.hub = hub
        self.cfg = cfg
        self.model_cfg = model_cfg
        self.model = build_model(model_cfg)
        self.prompts = prompts
        self.out_queue = out_queue
        self.stop_event = stop
        self.datacenter = datacenter
        self.is_spot = is_spot
        self.replica_name = name
        #: the device pulled weights land on and generation runs on
        #: (None: JAX's default device)
        self.device = device
        self.steps_done = 0
        self.weights_version: Optional[int] = None
        self.error: Optional[BaseException] = None

    def run(self) -> None:  # pragma: no cover - exercised via integration tests
        try:
            self._run()
        except BaseException as e:  # surfaced by the driver
            self.error = e

    def _run(self) -> None:
        cfg = self.cfg
        params = self.model.init(jax.random.PRNGKey(0), jnp.float32)
        handle = self.hub.open(
            cfg.model_name,
            self.replica_name,
            num_shards=1,
            shard_idx=0,
            datacenter=self.datacenter,
            is_spot=self.is_spot,
        )
        buffers = {k: np.array(v) for k, v in named_tensors(params).items()}
        handle.register(buffers)
        self.weights_version = handle.replicate("latest")
        rollout_step = 0
        while not self.stop_event.is_set():
            params = self._params_from_buffers(params, buffers)
            prompts = jax.device_put(
                self.prompts.sample(cfg.num_prompts * cfg.group_size, rollout_step),
                self.device,
            )
            key = jax.random.PRNGKey(hash((self.replica_name, rollout_step)) % (2**31))
            seqs, lps = sample_responses(self.model, params, prompts, cfg.response_len, key)
            rewards = self.prompts.reward(np.asarray(seqs), cfg.prompt_len)
            self.out_queue.append(
                {
                    "tokens": np.asarray(seqs),
                    "behavior_logprobs": np.asarray(lps),
                    "rewards": rewards,
                    "version": self.weights_version,
                    "worker": self.replica_name,
                }
            )
            self.steps_done += 1
            rollout_step += 1
            try:
                if handle.update("latest"):
                    self.weights_version = handle.current_version
            except (StaleHandleError, TensorHubError):
                break
        handle.close()

    def _params_from_buffers(self, params: Any, buffers: Dict[str, np.ndarray]) -> Any:
        flat = named_tensors(params)
        return jax.tree.unflatten(
            jax.tree.structure(params),
            [jax.device_put(buffers[k], self.device) for k in flat],
        )


class TrainerWorker:
    """Fig. 4a trainer side, driven synchronously by the example script."""

    def __init__(
        self,
        hub: TensorHubClient,
        cfg: RLConfig,
        model_cfg,
        rollout_queue: List,
        *,
        datacenter: str = "dc0",
    ) -> None:
        self.hub = hub
        self.cfg = cfg
        self.model_cfg = model_cfg
        self.model = build_model(model_cfg)
        self.queue = rollout_queue
        self.opt = AdamW(lr=cfg.lr, weight_decay=0.0)
        self.params = self.model.init(jax.random.PRNGKey(cfg.seed), jnp.float32)
        self.opt_state = self.opt.init(self.params)
        self.rl_step = jax.jit(make_grpo_step(self.model, model_cfg, self.opt))
        self.handle = hub.open(
            cfg.model_name, "trainer-0", num_shards=1, shard_idx=0,
            retain="latest", datacenter=datacenter,
        )
        self.version = 0
        self.metrics_log: List[Dict[str, float]] = []
        self._buffers = {k: np.array(v) for k, v in named_tensors(self.params).items()}
        self.handle.register(self._buffers)
        self._sync_buffers()
        self.handle.publish(self.version)

    def _sync_buffers(self) -> None:
        for k, v in named_tensors(self.params).items():
            np.copyto(self._buffers[k], np.asarray(v))

    def wait_for_rollouts(self, n: int, timeout: float = 120.0) -> List[Dict]:
        deadline = time.monotonic() + timeout
        while len(self.queue) < n:
            if time.monotonic() > deadline:
                raise TimeoutError("rollouts did not arrive in time")
            time.sleep(0.01)
        out = [self.queue.pop(0) for _ in range(n)]
        return out

    def train_on(self, rollouts: List[Dict]) -> Dict[str, float]:
        cfg = self.cfg
        tokens = np.concatenate([r["tokens"] for r in rollouts], axis=0)
        lps = np.concatenate([r["behavior_logprobs"] for r in rollouts], axis=0)
        rewards = np.concatenate([r["rewards"] for r in rollouts], axis=0)
        adv = group_relative_advantages(jnp.asarray(rewards), cfg.group_size)
        total = tokens.shape[1]
        # behavior logprobs cover response tokens only; align them into the
        # shifted [B, S-1] frame (position p-1 predicts token p)
        blp = np.zeros((tokens.shape[0], total - 1), np.float32)
        blp[:, cfg.prompt_len - 1 :] = lps
        loss_mask = np.zeros((tokens.shape[0], total - 1), bool)
        loss_mask[:, cfg.prompt_len - 1 :] = True
        batch = {
            "tokens": jnp.asarray(tokens),
            "behavior_logprobs": jnp.asarray(blp),
            "advantages": adv,
            "loss_mask": jnp.asarray(loss_mask),
        }
        # Fig. 4a: unpublish -> mutate -> publish the new version
        self.handle.unpublish()
        self.params, self.opt_state, metrics = self.rl_step(self.params, self.opt_state, batch)
        self._sync_buffers()
        self.version += 1
        self.handle.publish(self.version)
        out = {k: float(v) for k, v in metrics.items()}
        out["mean_reward"] = float(rewards.mean())
        out["version"] = self.version
        self.metrics_log.append(out)
        return out

    def close(self) -> None:
        self.handle.close()
