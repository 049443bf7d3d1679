#!/usr/bin/env python3
"""Chip smoke test: TensorHub's weight-update path on a TPU.

    python chip_smoke.py            # one chip: raw, int8, reshard and rl phases
    python chip_smoke.py --chips 4  # four chips: one rollout shard per chip

The weights are llama3-8b's (``src/repro/configs/llama3_8b.py``) at full
width in bf16, random bits made from ``--seed`` one tensor slice at a
time, so the whole 16 GB tree is never in host memory; their shapes come
from ``jax.eval_shape`` of the model's ``init``. The update runs through
the entry points a user calls: ``ReferenceServer``,
``TensorHubClient.open/register/publish/replicate/update`` and the
in-process ``LocalTransport``. Pulled weights go to the chip's HBM with
``jax.device_put``, and the Pallas checksum kernel verifies them there.

Phases (one chip):

- device: a TPU is required; anything else exits 1 before any work.
- raw: a trainer registers TP-2 shard 0 (8.0 GB) and publishes v0; a
  rollout in the same datacenter replicates it and lands it on the chip;
  the trainer writes v1 and the rollout updates to it.
- int8: a rollout in another datacenter pulls v1; the server negotiates
  int8 over the WAN, so the encode runs jitted on the chip. The bytes must
  equal ``Int8Codec(backend="numpy")``'s decode, bit for bit.
- reshard: a TP-2 rollout pulls from a TP-4 trainer over int8 and decodes
  with the fused device kernel (``device_repack=True``); the bytes must
  equal a NumPy-decoded pull's, and the kernel must have decoded units.
- rl: three GRPO steps of ``examples/rl_end_to_end.py`` with two rollouts.

With ``--chips 4`` only the multi-chip phase runs: two TP-2 rollout
replicas pull from a TP-2 trainer, and each of the four shards lands on
its own device, driven by one thread each in this one process.

Lines starting "smoke timing" are wall-clock timings of this run, not
benchmark metrics. The last line is one JSON object, printed only when
every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MODEL = "llama3-8b"
#: the raw and int8 phases move one TP-2 shard's slices as a model of its own
SHARD_MODEL = "llama3-8b/tp2-shard0"
#: seconds any one pull may take before the smoke calls it hung
PULL_TIMEOUT = 600.0
#: elements per seeded block of weight bits
_GEN_BLOCK = 1 << 22


def log(msg: str) -> None:
    print(msg, flush=True)


def meminfo() -> dict:
    """``/proc/meminfo`` in bytes."""
    with open("/proc/meminfo") as f:
        return {
            key: int(value.split()[0]) * 1024
            for key, value in (line.split(":", 1) for line in f)
        }


def host_mem_used() -> str:
    """The host's memory in use (``MemTotal`` less ``MemAvailable``). A
    process's RSS is no gauge on a TPU host: it counts device mappings."""
    m = meminfo()
    return f"host memory in use {(m['MemTotal'] - m['MemAvailable']) / 1e9:.2f} GB"


@contextmanager
def timed(label: str, nbytes: int = 0):
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    size = f" ({nbytes / 1e9:.3f} GB)" if nbytes else ""
    log(f"smoke timing: {label}: {dt:.2f} s{size} [{host_mem_used()}]")


def expect(ok: bool, what: str) -> None:
    """A check of the smoke's results (kept under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def host_mem_limit() -> int:
    """Host memory this process may use: ``MemTotal``, or the cgroup's
    limit where that is lower (a container reports the host's total)."""
    limit = meminfo()["MemTotal"]
    for path in (
        "/sys/fs/cgroup/memory.max",
        "/sys/fs/cgroup/memory/memory.limit_in_bytes",
    ):
        try:
            with open(path) as f:
                value = f.read().strip()
        except OSError:
            continue
        if value.isdigit():
            limit = min(limit, int(value))
    return limit


def run_threads(fns):
    """Run callables in one thread each; re-raise the first failure."""
    errors = []

    def wrap(fn):
        try:
            fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


# -- weights ------------------------------------------------------------------


def tree_shapes(cfg):
    """name -> ShapeDtypeStruct of the model's bf16 parameter tree."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model, named_tensors

    model = build_model(cfg)
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.bfloat16))
    return named_tensors(tree)


def shard_layout(shapes, shard: int, tp: int):
    """Local shape, first global element and layout entry of every
    tensor of one TP shard (``resharding.tp_shard``'s split rule)."""
    from repro.resharding.layout import tp_axis_for

    out = {}
    for name, sds in shapes.items():
        gshape = tuple(sds.shape)
        axis = tp_axis_for(name, gshape, tp)
        if axis is None:
            out[name] = (gshape, 0, (gshape, (0,) * len(gshape)))
            continue
        # axis-0 splits keep every shard's slice one contiguous range
        expect(axis == 0, f"{name} {gshape}: split on axis {axis}, not 0")
        per = gshape[0] // tp
        row = math.prod(gshape[1:])
        offset = (shard * per,) + (0,) * (len(gshape) - 1)
        out[name] = ((per,) + gshape[1:], shard * per * row, (gshape, offset))
    return out


def fill_weights(bits, seed: int, version: int, tensor: int, start: int) -> None:
    """Random finite bf16 bits for global elements [start, start + n) of
    one tensor: the same element gets the same bits in every layout.
    Sign and mantissa are random, the exponent spans 2^-15 .. 2^-8."""
    import numpy as np

    pos, n = 0, bits.size
    block = start // _GEN_BLOCK
    while pos < n:
        gen = np.random.PCG64(np.random.SeedSequence([seed, version, tensor, block]))
        raw = gen.random_raw(_GEN_BLOCK // 4).view(np.uint16)
        lo = start + pos - block * _GEN_BLOCK
        take = min(_GEN_BLOCK - lo, n - pos)
        out = bits[pos : pos + take]
        np.bitwise_and(raw[lo : lo + take], 0x83FF, out=out)
        np.bitwise_or(out, 0x3800, out=out)
        pos += take
        block += 1


def make_shard(shapes, shard: int, tp: int, seed: int, version: int):
    """(buffers, layout) of one TP shard, generated one tensor at a time."""
    import numpy as np

    bufs, layout = {}, {}
    for i, (name, (shape, start, lay)) in enumerate(
        shard_layout(shapes, shard, tp).items()
    ):
        arr = np.empty(shape, shapes[name].dtype)
        fill_weights(arr.view(np.uint16).reshape(-1), seed, version, i, start)
        bufs[name], layout[name] = arr, lay
    return bufs, layout


def rewrite_shard(bufs, shapes, shard: int, tp: int, seed: int, version: int):
    """Write another version's bits into a shard's buffers in place."""
    import numpy as np

    for i, (name, (_, start, _)) in enumerate(
        shard_layout(shapes, shard, tp).items()
    ):
        fill_weights(bufs[name].view(np.uint16).reshape(-1), seed, version, i, start)


def zeros_like_shard(shapes, shard: int, tp: int):
    import numpy as np

    lay = shard_layout(shapes, shard, tp)
    return (
        {n: np.zeros(s, shapes[n].dtype) for n, (s, _, _) in lay.items()},
        {n: entry for n, (_, _, entry) in lay.items()},
    )


def nbytes(bufs) -> int:
    return sum(a.nbytes for a in bufs.values())


def retire(handle) -> None:
    """Close a rollout handle no later step reads, and free its host
    buffers. They are unregistered first, so ``close``'s unpublish
    snapshots no whole-shard delta base that nobody would read. (Not for
    the last holder of a version: its ``close`` offloads the shard.)"""
    handle.store.unregister()
    handle.close()


def bits_equal(a, b) -> bool:
    import numpy as np

    return a.shape == b.shape and np.array_equal(
        a.view(np.uint8).reshape(-1), b.view(np.uint8).reshape(-1)
    )


# -- landing on the device ----------------------------------------------------


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def land(bufs, device):
    """Copy a pulled tree into ``device``'s HBM; returns when it is there.
    One tensor at a time, so the host holds one tensor's transfer staging
    at most."""
    import jax

    landed = {}
    for name, arr in bufs.items():
        landed[name] = jax.device_put(arr, device).block_until_ready()
    return landed


def check_landed(landed, want, *, full_bytes: bool) -> int:
    """The device checksum kernel over every landed tensor must equal the
    host fold of the wanted bytes; with ``full_bytes`` the bytes are also
    read back and compared. Consumes ``landed``: each tensor leaves HBM
    (and its host read-back the host) once checked. Returns a digest of
    the per-tensor sums."""
    import numpy as np

    from repro.kernels.checksum import fold64, tensor_checksum
    from repro.transfer.checksum import checksum

    sums = []
    while landed:
        name, arr = landed.popitem()
        got = fold64(np.asarray(tensor_checksum(arr)))
        host = checksum(want[name])
        expect(got == host, f"{name}: device checksum {got:#x} != host {host:#x}")
        if full_bytes:
            expect(bits_equal(np.asarray(arr), want[name]), f"{name}: bytes differ")
        sums.append((name, host))
        arr.delete()
    return checksum(np.array([v for _, v in sorted(sums)], np.uint64))


# -- phases -------------------------------------------------------------------


def phase_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if len(devs) < chips:
        print(f"chip_smoke: needs {chips} chips, found {len(devs)}", file=sys.stderr)
        raise SystemExit(1)
    stats = devs[0].memory_stats() or {}
    log(
        f"device: {devs[0].device_kind}, count {len(devs)}, host MemTotal "
        f"{meminfo()['MemTotal'] / 2**30:.1f} GiB (limit {host_mem_limit() / 2**30:.1f}"
        f" GiB), HBM bytes_limit "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB"
    )
    return devs


def phase_raw_and_int8(shapes, device, seed: int) -> None:
    """TP-2 shard 0's slices, served as one-shard replicas: a TP-2 group
    read completes (and releases its source) only once both shards have
    pulled, and two whole TP-2 replicas plus the trainer's retired-version
    snapshot would not fit a one-chip host's memory."""
    import numpy as np

    from repro.core import ReferenceServer, TensorHubClient
    from repro.transfer.codec import Int8Codec

    hub = TensorHubClient(ReferenceServer())
    trainer = hub.open(SHARD_MODEL, "trainer", 1, 0, datacenter="dc0")
    with timed("generate TP-2 shard 0 v0"):
        tr_bufs, _ = make_shard(shapes, 0, 2, seed, 0)
    size = nbytes(tr_bufs)
    trainer.register(tr_bufs)
    with timed("raw.publish v0 (host checksums)", size):
        trainer.publish(0)

    rollout = hub.open(SHARD_MODEL, "rollout-dc0", 1, 0, datacenter="dc0")
    ro_bufs, _ = zeros_like_shard(shapes, 0, 2)
    rollout.register(ro_bufs)
    with timed("raw.replicate v0", size):
        expect(rollout.replicate("latest", timeout=PULL_TIMEOUT) == 0, "raw: v0")
    with timed("raw.land v0 in HBM", size):
        landed = land(ro_bufs, device)
    with timed("raw.verify v0 on device and read back", size):
        digest = check_landed(landed, tr_bufs, full_bytes=True)
    log(f"raw: v0 landed bit-exact, digest {digest:#018x}")

    # unpublish and update each snapshot the retiring version as a
    # delta base (a whole shard copy); no WAN delta reader will come for
    # v0, so each is dropped at once, as under memory pressure
    with timed("raw.unpublish v0 (delta-base snapshot)", size):
        trainer.unpublish()
    trainer.store.drop_base()
    with timed("generate v1 into the trainer's buffers"):
        rewrite_shard(tr_bufs, shapes, 0, 2, seed, 1)
    trainer.publish(1)
    with timed("raw.update to v1", size):
        expect(rollout.update("latest"), "raw: no update to v1")
    expect(rollout.current_version == 1, "raw: v1")
    rollout.store.drop_base()
    with timed("raw.land v1 in HBM", size):
        landed = land(ro_bufs, device)
    check_landed(landed, tr_bufs, full_bytes=False)
    retire(rollout)
    del ro_bufs
    log("raw: v1 landed bit-exact")

    # 256 MiB chunks bound the encode and decode temporaries of the four
    # reads in flight
    far = hub.open(
        SHARD_MODEL, "rollout-dc1", 1, 0, datacenter="dc1", chunk_bytes=256 << 20
    )
    far_bufs, _ = zeros_like_shard(shapes, 0, 2)
    far.register(far_bufs)
    wire0 = hub.transport.wire_bytes.get("vpc_up", 0)
    with timed("int8.replicate v1 over the WAN (encode jitted on the chip)", size):
        expect(far.replicate("latest", timeout=PULL_TIMEOUT) == 1, "int8: v1")
    wire = hub.transport.wire_bytes.get("vpc_up", 0) - wire0
    expect(0 < wire < 0.6 * size, f"int8 pull moved {wire} WAN bytes for {size}")
    ref = Int8Codec(backend="numpy")

    def check_step(unit, dtype, o, step):
        # the codec quantizes transfer units (tiny tensors share one), in
        # row-aligned steps that encode exactly like the whole unit
        src = trainer.store.read_unit(unit)[o : o + step]
        got = far.store.read_unit(unit)[o : o + step]
        want = ref.decode(ref.encode(src, dtype))
        bad = np.flatnonzero(got != want)
        expect(
            bad.size == 0,
            f"unit {unit.name}: int8 pull differs from the NumPy codec in "
            f"{bad.size} bytes of [{o}, {o + step})",
        )

    steps = []
    for unit in trainer.store.units:
        dtype = trainer.store.unit_dtype(unit)
        step = 16 * 1024 * ref.row_bytes(dtype)
        steps += [(unit, dtype, o, step) for o in range(0, unit.nbytes, step)]
    gc.collect()
    log(f"int8: reference decode of {len(steps)} steps starts [{host_mem_used()}]")
    with timed("int8.reference decode with the NumPy codec", size):
        # NumPy releases the GIL in its array passes
        with ThreadPoolExecutor(max_workers=4) as pool:
            for _ in pool.map(lambda a: check_step(*a), steps):
                pass
    log(
        f"int8: v1 bit-identical to the NumPy codec, "
        f"{wire / size:.4f} WAN bytes per byte"
    )
    with timed("int8.land v1 in HBM", size):
        landed = land(far_bufs, device)
    check_landed(landed, far_bufs, full_bytes=False)
    retire(far)
    # the trainer holds the last copy of v1: closing it would offload the
    # shard to host memory, so its buffers go with the phase instead
    trainer.store.unregister()


def reshard_layers(cfg, mem_limit: int) -> int:
    """Layers the reshard phase keeps: the TP-4 source and two TP-2
    pulls each hold the whole tree in host memory, beside the encode and
    decode temporaries of up to four interval reads in flight. Cut depth
    (in multiples of 4, widths untouched) until three trees fit in 40%
    of the host memory limit."""
    shapes = tree_shapes(dataclasses.replace(cfg, num_layers=4))
    stacked = sum(
        math.prod(s.shape) * 2 for n, s in shapes.items() if n.startswith("layers/")
    )
    fixed = sum(math.prod(s.shape) * 2 for s in shapes.values()) - stacked
    layers = cfg.num_layers
    while layers > 4 and 3 * (fixed + layers * stacked / 4) > 0.4 * mem_limit:
        layers -= 4
    return layers


def phase_reshard(cfg, seed: int) -> None:
    import numpy as np

    from repro import obs
    from repro.core import ReferenceServer, TensorHubClient
    from repro.obs import telemetry

    layers = reshard_layers(cfg, host_mem_limit())
    if layers != cfg.num_layers:
        log(
            f"reshard: num_layers cut {cfg.num_layers} -> {layers} to fit host "
            f"memory (widths unchanged)"
        )
        cfg = dataclasses.replace(cfg, num_layers=layers)
    shapes = tree_shapes(cfg)
    rec = obs.Recorder()
    hub = TensorHubClient(ReferenceServer(), recorder=rec)
    src = [hub.open(MODEL, "trainer-tp4", 4, i, datacenter="dc0") for i in range(4)]
    with timed("reshard.generate TP-4 shards"):
        for h in src:
            bufs, layout = make_shard(shapes, h.shard_idx, 4, seed, 0)
            h.register(bufs, layout=layout)
    run_threads([lambda h=h: h.publish(0) for h in src])

    def pull(name, device_repack):
        hs = [
            hub.open(MODEL, name, 2, i, datacenter="dc1", device_repack=device_repack)
            for i in range(2)
        ]
        bufs = []
        for h in hs:
            b, layout = zeros_like_shard(shapes, h.shard_idx, 2)
            h.register(b, layout=layout)
            bufs.append(b)
        run_threads([
            lambda h=h: h.replicate("latest", timeout=PULL_TIMEOUT) for h in hs
        ])
        return hs, bufs

    def units():
        return (
            rec.counter(telemetry.CTR_DECODE_KERNEL_UNITS),
            rec.counter(telemetry.CTR_DECODE_HOST_UNITS),
        )

    size = sum(math.prod(s.shape) * 2 for s in shapes.values())
    with timed("reshard.TP-4 -> TP-2 int8 pull, fused device decode", size):
        kern, kern_bufs = pull("rollout-kernel", True)
    k_units, h_units = units()
    log(f"reshard: fused decodes on the device {k_units:.0f}, on the host {h_units:.0f}")
    expect(k_units > 0 and h_units == 0, "the device kernel did not decode every unit")
    # retired, so the NumPy pull cannot copy the kernel pull's same-layout
    # bytes and must reshard from the TP-4 trainer too
    for h in kern:
        retire(h)
    with timed("reshard.TP-4 -> TP-2 int8 pull, NumPy decode", size):
        ref, ref_bufs = pull("rollout-numpy", False)
    expect(units() == (k_units, k_units), f"NumPy reshard decodes {units()}")
    for shard, (a, b) in enumerate(zip(kern_bufs, ref_bufs)):
        for name in shapes:
            expect(
                bits_equal(a[name], b[name]),
                f"shard {shard} {name}: kernel != NumPy",
            )
    log("reshard: device-decoded TP-2 shards bit-identical to the NumPy decode")
    for h in ref:
        retire(h)
    for h in src:
        h.store.unregister()


def phase_rl() -> None:
    spec = importlib.util.spec_from_file_location(
        "rl_end_to_end", ROOT / "examples" / "rl_end_to_end.py"
    )
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    with timed("rl.3 GRPO steps, 2 rollout workers"):
        metrics = example.main(["--steps", "3", "--rollout-workers", "2"])
    versions = [m["version"] for m in metrics]
    expect(versions == [1, 2, 3], f"rl: versions {versions}")
    expect(all(math.isfinite(m["loss"]) for m in metrics), f"rl: {metrics}")
    log(f"rl: 3 steps, losses {[round(m['loss'], 4) for m in metrics]}")


def phase_four_chips(shapes, devs, seed: int) -> None:
    """Two TP-2 rollout replicas pull from a TP-2 trainer; shard s of
    replica r lands on device 2r + s."""
    from repro.core import ReferenceServer, TensorHubClient

    hub = TensorHubClient(ReferenceServer())
    trainer = [hub.open(MODEL, "trainer", 2, i, datacenter="dc0") for i in range(2)]
    src = {}
    with timed("generate TP-2 shards 0 and 1 v0"):
        for h in trainer:
            src[h.shard_idx], layout = make_shard(shapes, h.shard_idx, 2, seed, 0)
            h.register(src[h.shard_idx], layout=layout)
    run_threads([lambda h=h: h.publish(0) for h in trainer])

    pulls = []
    for r in range(2):
        for s in range(2):
            h = hub.open(MODEL, f"rollout-{r}", 2, s, datacenter="dc0")
            bufs, layout = zeros_like_shard(shapes, s, 2)
            h.register(bufs, layout=layout)
            pulls.append((h, bufs, devs[2 * r + s]))
    before = {d.id: bytes_in_use(d) for d in devs[:4]}
    results = {}

    def pull_and_land(h, bufs, dev):
        expect(h.replicate("latest", timeout=PULL_TIMEOUT) == 0, f"{h.replica}: v0")
        landed = land(bufs, dev)
        on = {d for arr in landed.values() for d in arr.devices()}
        grew = bytes_in_use(dev) - before[dev.id]
        digest = check_landed(landed, src[h.shard_idx], full_bytes=True)
        results[dev.id] = (h, on, grew, digest)

    size = 2 * nbytes(src[0])
    with timed("4 chips: 4 shard pulls landed in HBM, one thread each", size):
        run_threads([lambda p=p: pull_and_land(*p) for p in pulls])
    for dev in devs[:4]:
        h, on, grew, digest = results[dev.id]
        expect(
            on == {dev} and grew >= nbytes(src[h.shard_idx]),
            f"device {dev.id}: arrays on {on}, bytes_in_use grew {grew}",
        )
        log(
            f"4 chips: device {dev.id} holds {h.replica} shard {h.shard_idx}, "
            f"bytes_in_use +{grew / 1e9:.3f} GB, bytes read back equal the "
            f"trainer's, digest {digest:#018x}"
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = phase_device(args.chips)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(MODEL)
    shapes = tree_shapes(cfg)
    log(
        f"{MODEL}: {len(shapes)} tensors, "
        f"{sum(math.prod(s.shape) * 2 for s in shapes.values()) / 1e9:.2f} GB bf16"
    )
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(shapes, devs, args.seed)
    else:
        phase_raw_and_int8(shapes, devs[0], args.seed)
        phase_reshard(cfg, args.seed)
        phase_rl()
    log(f"smoke timing: all phases: {time.perf_counter() - t0:.2f} s")
    result = {
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
