"""One run of a cell: a trainer publishing versions, rollouts pulling them.

The cell's configuration says what the trainer and each rollout hold; its
traffic mix (``mixes/<name>.json``) where they sit, how many rollout
replicas there are, what share of the elements a version changes and how
many untimed updates set-up runs. Everything goes through the program's entry
points under their defaults: ``ReferenceServer()``, ``TensorHubClient``
with its ``LocalTransport``, and ``open/register/publish/unpublish/
replicate/update`` on the handles.

The loop is closed: one update at a time. An update is

1. the trainer's ``unpublish()`` (each shard in a thread of its own),
2. the trainer's bits rewritten to the next version (the stand-in for the
   optimizer step; not part of any metric),
3. the trainer's ``publish(k)``,
4. every rollout's ``update("latest")``, then ``land_rollout`` onto its
   chip, each replica in a thread of its own.

``sync_s`` is (1) + (3) + (4): the weight-sync critical path of a
synchronous RL step.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List

import jax
import ml_dtypes
import numpy as np

import deployment as dep
import gen
from land import land_rollout

#: seconds a first pull may take before the run calls it hung
PULL_TIMEOUT = 600.0
#: the program's stall counters read per update (``repro.obs.telemetry``)
COUNTERS = ("stall/plan_wait", "stall/control", "stall/verify", "stall/decode")


def run_threads(fns: List[Callable[[], object]]) -> list:
    """Run callables in one thread each (inline when there is one);
    re-raise the first failure."""
    if len(fns) == 1:
        return [fns[0]()]
    out: list = [None] * len(fns)
    errors: list = []

    def wrap(i, fn):
        try:
            out[i] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(i, fn)) for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


class Cell:
    """The deployment of one cell in this process, and its update loop."""

    def __init__(
        self,
        cfg: dict,
        mix: dict,
        seed: int,
        devices: List[jax.Device],
        *,
        recorder=None,
        annotate: bool = False,
    ) -> None:
        from repro.core import ReferenceServer, TensorHubClient

        if cfg["torch_dtype"] != "bfloat16":
            raise ValueError(f"{cfg['name']}: weights are made in bfloat16 only")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.fraction = float(mix["changed_fraction"])
        self.recorder = recorder
        self.annotate = annotate
        self.hub = TensorHubClient(ReferenceServer(), recorder=recorder)
        model = cfg["name"]
        shards = dep.trainer_slices(cfg)
        self.trainer_slices = shards
        self.trainer = [
            self.hub.open(model, "trainer", len(shards), i, datacenter=mix["trainer_datacenter"])
            for i in range(len(shards))
        ]
        self.trainer_bufs = [
            {s.name: np.empty(s.shape, ml_dtypes.bfloat16) for s in shard} for shard in shards
        ]
        self.rollout_slices = dep.rollout_slices(cfg)
        replicas = int(mix["replicas"])
        if len(devices) < replicas:
            raise ValueError(f"{replicas} rollout replicas need {replicas} devices")
        self.devices = devices[:replicas]
        self.rollouts = [
            self.hub.open(model, f"rollout-{r}", 1, 0, datacenter=mix["rollout_datacenter"])
            for r in range(replicas)
        ]
        self.landed: List[Dict[str, jax.Array]] = [{} for _ in self.rollouts]
        #: per version, per replica: [(tensor name, device digest)]
        self.digests: Dict[int, List[list]] = {}
        self.version = -1

    # -- pieces of an update ----------------------------------------------------

    def _span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.annotate else contextlib.nullcontext()

    def write_version(self, k: int) -> None:
        for shard, bufs in zip(self.trainer_slices, self.trainer_bufs):
            for s, bits in gen.host_blocks(shard, self.seed, k, self.fraction):
                bufs[s.name].view(np.uint16)[...] = bits

    def _publish(self, k: int) -> None:
        run_threads([lambda h=h: h.publish(k) for h in self.trainer])

    def _unpublish(self) -> None:
        run_threads([h.unpublish for h in self.trainer])

    def _pull_and_land(self, r: int, first: bool) -> tuple:
        h, dev = self.rollouts[r], self.devices[r]
        t0 = time.perf_counter()
        with self._span("pull"):
            if first:
                got = h.replicate("latest", timeout=PULL_TIMEOUT)
            else:
                if not h.update("latest"):
                    raise RuntimeError(f"{h.replica}: update to v{self.version} found nothing")
                got = h.current_version
        if got != self.version:
            raise RuntimeError(f"{h.replica}: holds v{got}, not v{self.version}")
        t1 = time.perf_counter()
        with self._span("land"):
            land_rollout(h, dev, self.landed[r])
        t2 = time.perf_counter()
        return t1 - t0, t2 - t1

    def _digest(self) -> None:
        with self._span("digest"):
            self.digests[self.version] = [
                [(name, gen.digest(arr)) for name, arr in landed.items()]
                for landed in self.landed
            ]

    def _counters(self) -> Dict[str, float]:
        if self.recorder is None:
            return {}
        return {c: self.recorder.counter(c) for c in COUNTERS}

    # -- set-up and the loop --------------------------------------------------------

    def start(self) -> None:
        """Version 0: made, registered, published, pulled and landed."""
        self.version = 0
        self.write_version(0)
        for h, shard, bufs in zip(self.trainer, self.trainer_slices, self.trainer_bufs):
            h.register(bufs, layout={s.name: s.layout for s in shard})
        self._publish(0)
        for h in self.rollouts:
            h.register(
                {s.name: np.zeros(s.shape, ml_dtypes.bfloat16) for s in self.rollout_slices},
                layout={s.name: s.layout for s in self.rollout_slices},
            )
        run_threads([lambda r=r: self._pull_and_land(r, True) for r in range(len(self.rollouts))])
        self._digest()

    def update(self) -> Dict[str, float]:
        """One update to the next version; returns its timings."""
        k = self.version + 1
        c0 = self._counters()
        t0 = time.perf_counter()
        with self._span("publish"):
            self._unpublish()
        t1 = time.perf_counter()
        with self._span("rewrite"):
            self.write_version(k)
        t2 = time.perf_counter()
        with self._span("publish"):
            self._publish(k)
        t3 = time.perf_counter()
        self.version = k
        times = run_threads(
            [lambda r=r: self._pull_and_land(r, False) for r in range(len(self.rollouts))]
        )
        t4 = time.perf_counter()
        self._digest()
        c1 = self._counters()
        return {
            "version": k,
            "sync_s": (t1 - t0) + (t3 - t2) + (t4 - t3),
            "publish_s": (t1 - t0) + (t3 - t2),
            "rewrite_s": t2 - t1,
            "pull_s": max(p for p, _ in times),
            "land_s": max(ld for _, ld in times),
            **{f"ctr:{c}": c1[c] - c0[c] for c in c1},
        }

    def window(self, seconds: float) -> List[Dict[str, float]]:
        """Updates, a new one starting while less than ``seconds`` have
        passed; the one in flight at the end finishes and counts."""
        out: List[Dict[str, float]] = []
        t0 = time.perf_counter()
        while not out or time.perf_counter() - t0 < seconds:
            out.append(self.update())
        return out

    def peak_hbm(self) -> int:
        return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())

    def release(self) -> None:
        """Free the program's host copies (the reference runs after)."""
        for h in self.trainer + self.rollouts:
            h.store.drop_base()
            h.store.unregister()
        self.trainer_bufs = []


def expected_versions(cell: Cell, control: bool = False):
    """Yield ``(k, {name: uint16 bits})``: what a rollout must hold after
    version k, for every version the run pulled. Over a raw link that is
    the version itself; the control computes it in the next precision
    down, each tensor's int8 round trip."""
    import reference as ref

    if cell.mix["rollout_datacenter"] != cell.mix["trainer_datacenter"]:
        raise NotImplementedError("the reference models the raw same-datacenter wire only")
    for k in range(cell.version + 1):
        vk = {
            s.name: bits
            for s, bits in gen.host_blocks(cell.rollout_slices, cell.seed, k, cell.fraction)
        }
        if control:
            vk = {n: ref.round_trip(b.reshape(-1)).reshape(b.shape) for n, b in vk.items()}
        yield k, vk


def _digest_of(bits: np.ndarray) -> np.ndarray:
    """The digest of the reference's bytes, on the host: wrapping integer
    sums come out the same on every backend."""
    return np.asarray(gen.digest(jax.device_put(bits, gen.host_device())))


def compare(cell: Cell, control: bool = False) -> Dict[str, int]:
    """The numbers ``correct`` is decided by, each with limit 0:

    - ``digest_mismatches``: tensors, over every version pulled and every
      replica, whose device digest of the landed bytes differs from the
      digest of the reference's bytes;
    - ``final_mismatched_elements``: elements of the last version, read
      back from every replica's HBM, that differ from the reference's.

    With ``control`` the reference computed in a lower precision takes
    the program's place, and must fail."""
    bad_digests = 0
    bad_elements = 0
    ctrl = expected_versions(cell, control=True) if control else None
    for k, want in expected_versions(cell):
        got = next(ctrl)[1] if ctrl is not None else None
        for per_replica, landed in zip(cell.digests[k], cell.landed):
            for name, d in per_replica:
                mine = _digest_of(got[name]) if got is not None else np.asarray(d)
                bad_digests += int(not np.array_equal(mine, _digest_of(want[name])))
            if k == cell.version:
                for name, bits in want.items():
                    held = got[name] if got is not None else np.asarray(landed[name]).view(np.uint16)
                    bad_elements += int(np.count_nonzero(held != bits))
    return {"digest_mismatches": bad_digests, "final_mismatched_elements": bad_elements}
