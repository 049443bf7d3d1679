"""Cross-layout resharding: planner tiling properties over the paper
workload configs, end-to-end reshard-replicate bytes equality, repack
kernel-vs-ref parity, and failure re-planning in virtual time."""

import dataclasses
import random
import threading
import time
from unittest import mock

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.paper_workloads import WORKLOADS
from repro.core import ReferenceServer, ShardLayoutError, TensorHubClient
from repro.core.meta import ShardManifest, TensorMeta, build_units
from repro.resharding import (
    layout_from_manifests,
    plan_reshard,
    plan_shard,
    planner,
    rowgrid,
    tp_shard,
)
from repro.resharding.layout import (
    ReplicaLayout,
    ShardSlice,
    TensorLayout,
    dtype_itemsize,
)
from repro.transfer.codec import get_codec
from repro.transfer.simcluster import SimCluster, make_layout_manifests

TP_DEGREES = [1, 2, 3, 4, 8]


def model_tensors(seed=0):
    """A small model with mixed ranks: dim-0 shardable, dim-1 shardable
    (first dim indivisible by most TPs), and a replicated odd-size bias."""
    rng = np.random.default_rng(seed)
    return {
        "wqkv": rng.standard_normal((24, 16)).astype(np.float32),
        "wout": rng.standard_normal((7, 24)).astype(np.float32),  # dim-1 shard
        "embed": rng.standard_normal((48,)).astype(np.float32),
        "bias": rng.standard_normal((5,)).astype(np.float32),  # replicated
    }


def manifest_for(local, lay, with_checksums=False):
    metas = [
        TensorMeta(
            name=n,
            shape=tuple(a.shape),
            dtype=str(a.dtype),
            nbytes=a.nbytes,
            global_shape=lay[n][0],
            offset=lay[n][1],
        )
        for n, a in local.items()
    ]
    units = build_units(metas)
    return ShardManifest(
        tensors=tuple(metas), units=tuple(units), checksums=(0,) * len(units)
    )


def layouts_for(glob, tp):
    ms = {i: manifest_for(*tp_shard(glob, i, tp)) for i in range(tp)}
    return layout_from_manifests(ms, tp)


class TestPlannerProperties:
    @pytest.mark.parametrize("src_tp", TP_DEGREES)
    @pytest.mark.parametrize("dst_tp", TP_DEGREES)
    def test_exact_tiling_and_value_identity(self, src_tp, dst_tp):
        """Every (source, dest) TP pair: intervals tile each dest tensor
        exactly (validated by the planner) and executing them against the
        source buffers reproduces the dest slices bit for bit."""
        glob = model_tensors()
        plan = plan_reshard(
            layouts_for(glob, src_tp), layouts_for(glob, dst_tp), stripe_min=16
        )
        src_locals = [tp_shard(glob, j, src_tp)[0] for j in range(src_tp)]
        for sp in plan.shards:
            d_local, _ = tp_shard(glob, sp.dest_shard, dst_tp)
            for name, want in d_local.items():
                out = np.zeros(want.nbytes, np.uint8)
                for iv in sp.intervals:
                    if iv.tensor != name:
                        continue
                    src = src_locals[iv.source_shard][name].view(np.uint8).reshape(-1)
                    out[iv.dst_offset : iv.dst_stop] = src[iv.src_offset : iv.src_stop]
                assert np.array_equal(out, want.view(np.uint8).reshape(-1)), (
                    src_tp, dst_tp, sp.dest_shard, name,
                )

    @pytest.mark.parametrize("wname", sorted(WORKLOADS))
    @pytest.mark.parametrize("dst_tp", [2, 8])
    def test_paper_workload_layouts_tile(self, wname, dst_tp):
        """1-D contiguous layouts at paper-workload sizes: plans tile and
        byte totals match the destination's share exactly."""
        w = WORKLOADS[wname]
        units = [b * w.num_shards for b in w.unit_bytes(8)]
        src = layout_from_manifests(
            dict(enumerate(make_layout_manifests(units, w.num_shards))),
            w.num_shards,
        )
        dst = layout_from_manifests(
            dict(enumerate(make_layout_manifests(units, dst_tp))), dst_tp
        )
        plan = plan_reshard(src, dst)
        assert plan.total_bytes == sum(units)
        for sp in plan.shards:
            assert sp.total_bytes == sum(
                m.total_bytes
                for i, m in enumerate(make_layout_manifests(units, dst_tp))
                if i == sp.dest_shard
            )

    @settings(max_examples=25, deadline=None)
    @given(
        src_tp=st.sampled_from(TP_DEGREES),
        dst_tp=st.sampled_from(TP_DEGREES),
        sizes=st.lists(st.integers(64, 4096), min_size=1, max_size=5),
        seed=st.integers(0, 1000),
    )
    def test_random_1d_layouts_tile(self, src_tp, dst_tp, sizes, seed):
        """Property sweep: random global unit sizes, any TP pair — the
        planner's own validation (no gaps/overlaps) must hold and byte
        totals must be conserved."""
        del seed  # layouts are deterministic given sizes; kept for draw variety
        src = layout_from_manifests(
            dict(enumerate(make_layout_manifests(sizes, src_tp))), src_tp
        )
        dst = layout_from_manifests(
            dict(enumerate(make_layout_manifests(sizes, dst_tp))), dst_tp
        )
        plan = plan_reshard(src, dst, stripe_min=32)
        assert plan.total_bytes == sum(sizes)

    def test_striping_across_sources(self):
        """Scale-down: a dest shard's slice spans several source shards;
        the plan must stripe across >= 2 of them (acceptance criterion)."""
        glob = model_tensors()
        plan = plan_reshard(layouts_for(glob, 4), layouts_for(glob, 2), stripe_min=16)
        for sp in plan.shards:
            assert len(sp.source_shards_used) >= 2, sp.dest_shard

    def test_incompatible_layouts_raise(self):
        glob = model_tensors()
        other = {k: v for k, v in glob.items() if k != "bias"}
        with pytest.raises(ShardLayoutError):
            plan_reshard(layouts_for(other, 2), layouts_for(glob, 4))
        # same names, different global shape
        resized = dict(glob)
        resized["embed"] = np.zeros((64,), np.float32)
        with pytest.raises(ShardLayoutError):
            plan_reshard(layouts_for(resized, 2), layouts_for(glob, 2))

    def test_missing_descriptor_needs_identical_shape(self):
        """No layout metadata -> treated as replicated; convertible only
        when local shapes agree."""
        a = {0: manifest_for({"w": np.zeros((4, 4), np.float32)},
                             {"w": (None, None)})}
        b = {0: manifest_for({"w": np.zeros((2, 4), np.float32)},
                             {"w": (None, None)})}
        with pytest.raises(ShardLayoutError):
            plan_shard(layout_from_manifests(a, 1), layout_from_manifests(b, 1), 0)


# ---------------------------------------------------------------------------
# coverage sweep: cursor per source shard against a plain full scan
# ---------------------------------------------------------------------------


def _reference_plan_tensor(tensor, dest_slice, load, *, stripe_min, codec="raw"):
    """Plain coverage sweep: every segment scans every run of every
    source shard from the start, O(segments x runs). Otherwise the
    planner's own rules: candidates in ``tensor.slices`` order, the
    least-loaded choice with ties to the lower shard index, striping of
    multiply covered regions, row-grid widening, ``load`` bookkeeping."""
    local_bytes = tensor.itemsize
    for d in dest_slice.shape or (1,):
        local_bytes *= d
    if local_bytes == 0:
        return []
    runs, place, rb_of = {}, {}, {}
    wire = get_codec(codec)
    for src_slice in tensor.slices:
        r = planner._intersection_runs(dest_slice, src_slice, tensor.itemsize)
        if r:
            runs[src_slice.shard] = r
            place[src_slice.shard] = src_slice
            rb_of[src_slice.shard] = wire.row_bytes(src_slice.unit_dtype)
    cuts = {0, local_bytes}
    for rs in runs.values():
        for dst_off, _, nbytes in rs:
            cuts.update((dst_off, dst_off + nbytes))
    edges = sorted(c for c in cuts if 0 <= c <= local_bytes)
    intervals = []

    def emit(shard, dst_a, dst_b, src_off):
        p = place[shard]
        unit_off = p.unit_offset + src_off
        lead, tail = rowgrid.snap(unit_off, dst_b - dst_a, rb_of[shard], p.unit_nbytes)
        intervals.append(
            planner.ReadInterval(
                tensor=tensor.name,
                source_shard=shard,
                src_offset=src_off,
                dst_offset=dst_a,
                nbytes=dst_b - dst_a,
                source_unit=p.unit,
                dest_unit=dest_slice.unit,
                src_unit_offset=unit_off,
                src_unit_nbytes=p.unit_nbytes,
                lead=lead,
                tail=tail,
            )
        )
        load[shard] = load.get(shard, 0) + (dst_b - dst_a)

    for a, b in zip(edges[:-1], edges[1:]):
        cands = []
        for shard, rs in runs.items():
            for dst_off, src_off, nbytes in rs:
                if dst_off <= a and b <= dst_off + nbytes:
                    cands.append((shard, src_off + (a - dst_off)))
                    break
        if not cands:
            raise ShardLayoutError(
                f"tensor {tensor.name!r}: destination bytes [{a}, {b}) of "
                f"shard {dest_slice.shard} are not covered by any source "
                "shard (layouts not convertible)"
            )
        if len(cands) == 1 or b - a < 2 * stripe_min:
            shard, src_off = min(cands, key=lambda c: (load.get(c[0], 0), c[0]))
            emit(shard, a, b, src_off)
            continue
        n_stripes = min(len(cands), max(2, (b - a) // stripe_min))
        per = rowgrid.chunk_align((b - a) // n_stripes, max(rb_of[s] for s, _ in cands))
        order = sorted(cands, key=lambda c: (load.get(c[0], 0), c[0]))
        pos, k = a, 0
        while pos < b:
            stop = b if k >= n_stripes - 1 else min(pos + per, b)
            shard, src_base = order[k % len(order)]
            emit(shard, pos, stop, src_base + (pos - a))
            pos, k = stop, k + 1
    return intervals


#: layout families the cursor sweep is checked on; ``gap`` leaves one
#: source block out, so some destination bytes have no source
PLAN_KINDS = [
    "rows_to_cols",
    "cols_to_rows",
    "uneven_grid",
    "replicated",
    "overlapping",
    "gap",
]


def _blocks(rng, n, parts):
    """Uneven ``[lo, hi)`` blocks tiling ``[0, n)``, at most ``parts``."""
    inner = sorted(rng.sample(range(1, n), min(parts, n) - 1))
    cuts = [0, *inner, n]
    return list(zip(cuts[:-1], cuts[1:]))


def _plan_case(kind, seed):
    """One tensor's source layout and one destination slice of it, drawn
    from ``seed``: shard ids out of 0..7 in shuffled ``slices`` order,
    unit placements with member offsets and trailing bytes, and a unit
    dtype the int8 codec may or may not quantize."""
    rng = random.Random(seed)
    dtype = rng.choice(["bfloat16", "float32", "int32"])
    itemsize = dtype_itemsize(dtype)
    rows, cols = rng.randint(2, 16), rng.randint(2, 700)
    blocks = []  # (start, shape) in global coordinates
    dest = ((0, 0), (rows, cols))
    if kind == "rows_to_cols":
        blocks = [((r0, 0), (r1 - r0, cols)) for r0, r1 in _blocks(rng, rows, 4)]
        c0, c1 = rng.choice(_blocks(rng, cols, 8))
        dest = ((0, c0), (rows, c1 - c0))
    elif kind == "cols_to_rows":
        blocks = [((0, c0), (rows, c1 - c0)) for c0, c1 in _blocks(rng, cols, 4)]
        r0, r1 = rng.choice(_blocks(rng, rows, 8))
        dest = ((r0, 0), (r1 - r0, cols))
    elif kind == "uneven_grid":
        blocks = [
            ((r0, c0), (r1 - r0, c1 - c0))
            for r0, r1 in _blocks(rng, rows, 3)
            for c0, c1 in _blocks(rng, cols, 2)
        ]
    elif kind == "replicated":
        if rng.random() < 0.5:
            rows, cols = 1, rows * cols
        blocks = [((0, 0), (rows, cols))] * rng.randint(2, 4)
        if rng.random() < 0.5:  # and a partial copy, splitting the full runs
            if rows == 1:
                c0, c1 = sorted(rng.sample(range(cols + 1), 2))
                blocks.append(((0, c0), (1, c1 - c0)))
            else:
                r0, r1 = sorted(rng.sample(range(rows + 1), 2))
                blocks.append(((r0, 0), (r1 - r0, cols)))
    elif kind == "overlapping":
        for r0, r1 in _blocks(rng, rows, 4):
            r0, r1 = max(0, r0 - rng.randint(0, 2)), min(rows, r1 + rng.randint(0, 2))
            blocks.append(((r0, 0), (r1 - r0, cols)))
        if rng.random() < 0.5:
            blocks.append(((0, 0), (rows, cols)))
    elif kind == "gap":
        parts = _blocks(rng, rows, 4)
        if len(parts) == 1:
            parts = [(0, 1), (1, rows)]
        del parts[rng.randrange(len(parts))]
        blocks = [((r0, 0), (r1 - r0, cols)) for r0, r1 in parts]
    if kind in ("uneven_grid", "replicated", "overlapping"):
        r0, r1 = sorted(rng.sample(range(rows + 1), 2))
        c0, c1 = sorted(rng.sample(range(cols + 1), 2))
        if rng.random() < 0.5:  # whole rows: a source's rows merge to one run
            c0, c1 = 0, cols
        dest = ((r0, c0), (r1 - r0, c1 - c0))
    if rows == 1:  # replicated 1-D
        blocks = [((start[1],), (shape[1],)) for start, shape in blocks]
        dest = ((dest[0][1],), (dest[1][1],))
        gshape = (cols,)
    else:
        gshape = (rows, cols)
    slices = []
    for shard, (start, shape) in zip(rng.sample(range(8), len(blocks)), blocks):
        nbytes = itemsize * int(np.prod(shape))
        unit_offset = itemsize * rng.randrange(600)
        slices.append(
            ShardSlice(
                shard=shard,
                start=start,
                shape=shape,
                unit=rng.randrange(4),
                unit_offset=unit_offset,
                unit_nbytes=unit_offset + nbytes + itemsize * rng.randrange(600),
                unit_dtype=rng.choice([dtype, dtype, None]),
            )
        )
    rng.shuffle(slices)
    tensor = TensorLayout(
        name=f"{kind}.{seed}",
        dtype=dtype,
        itemsize=itemsize,
        global_shape=gshape,
        slices=tuple(slices),
    )
    d_slice = ShardSlice(
        shard=rng.randrange(8), start=dest[0], shape=dest[1], unit=rng.randrange(4)
    )
    return tensor, d_slice


def _drawn_load(rng):
    """Bytes already assigned per source shard, with ties."""
    return {s: rng.choice([0, 64, 512]) for s in range(8) if rng.random() < 0.5}


class TestCursorSweep:
    @pytest.mark.parametrize("kind", PLAN_KINDS)
    @settings(max_examples=40, deadline=None)
    @given(
        seed=st.integers(0, 2**31),
        stripe_min=st.sampled_from([8, 64, 512, planner.STRIPE_MIN_BYTES]),
        codec=st.sampled_from(["raw", "int8"]),
    )
    def test_plan_tensor_matches_full_scan(self, kind, seed, stripe_min, codec):
        """The cursor sweep emits the full scan's intervals, in order, and
        leaves the same per-shard load; an uncovered segment raises the
        same ShardLayoutError after the same assignments."""
        tensor, d_slice = _plan_case(kind, seed)
        load0 = _drawn_load(random.Random(seed + 1))
        outcome = []
        for fn in (_reference_plan_tensor, planner._plan_tensor):
            load = dict(load0)
            try:
                got = tuple(fn(tensor, d_slice, load, stripe_min=stripe_min, codec=codec))
            except ShardLayoutError as e:
                got = str(e)
            outcome.append((got, load))
        assert outcome[1] == outcome[0]
        assert isinstance(outcome[0][0], str) == (kind == "gap")

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31),
        stripe_min=st.sampled_from([8, 64, 512, planner.STRIPE_MIN_BYTES]),
        codec=st.sampled_from(["raw", "int8"]),
    )
    def test_plan_shard_matches_full_scan(self, seed, stripe_min, codec):
        """A destination shard holding one tensor of every covered kind:
        the load carried from tensor to tensor steers each choice, and
        the whole ShardPlan equals the full scan's."""
        rng = random.Random(seed)
        src, dst = [], []
        for kind in PLAN_KINDS[:-1]:
            tensor, d_slice = _plan_case(kind, rng.randrange(2**31))
            src.append(tensor)
            dst.append(
                dataclasses.replace(tensor, slices=(dataclasses.replace(d_slice, shard=0),))
            )
        source = ReplicaLayout(num_shards=8, tensors=tuple(src))
        dest = ReplicaLayout(num_shards=1, tensors=tuple(dst))
        kw = dict(stripe_min=stripe_min, codec=codec)
        got = plan_shard(source, dest, 0, **kw)
        with mock.patch.object(planner, "_plan_tensor", _reference_plan_tensor):
            want = plan_shard(source, dest, 0, **kw)
        assert got == want

    def test_down_proj_plans_in_linear_time(self):
        """DeepSeek-Coder-33B's down_proj (7168 x 19200 bf16): trainer TP-4
        split on rows, rollout TP-8 shard 0 split on columns. Every one of
        the 7168 destination rows comes from one trainer shard; the full
        scan over every run takes ~3 s on a CPU core, the cursor sweep
        ~0.1 s."""
        rows, cols, isz = 7168, 19200, 2
        per = rows // 4
        tensor = TensorLayout(
            name="model.layers.0.mlp.down_proj.weight",
            dtype="bfloat16",
            itemsize=isz,
            global_shape=(rows, cols),
            slices=tuple(
                ShardSlice(
                    shard=j,
                    start=(j * per, 0),
                    shape=(per, cols),
                    unit=7,
                    unit_nbytes=per * cols * isz,
                    unit_dtype="bfloat16",
                )
                for j in range(4)
            ),
        )
        d_slice = ShardSlice(shard=0, start=(0, 0), shape=(rows, cols // 8), unit=7)
        best = float("inf")
        for _ in range(3):  # best of three: a loaded CPU only adds time
            t0 = time.perf_counter()
            ivs = planner._plan_tensor(
                tensor, d_slice, {}, stripe_min=planner.STRIPE_MIN_BYTES
            )
            best = min(best, time.perf_counter() - t0)
            if best < 1.5:
                break
        assert len(ivs) == rows
        assert [iv.source_shard for iv in ivs] == [r // per for r in range(rows)]
        assert best < 1.5, f"planning one down_proj took {best:.2f} s"


# ---------------------------------------------------------------------------
# end-to-end: threaded client
# ---------------------------------------------------------------------------


def run_group(handles, fn):
    errs = []

    def wrap(h):
        try:
            fn(h)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(h,)) for h in handles]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    if errs:
        raise errs[0]


def open_tp_group(hub, name, tp, glob, *, zeros=False, **kw):
    handles = [hub.open("m", name, tp, i, **kw) for i in range(tp)]
    for h in handles:
        local, lay = tp_shard(glob, h.shard_idx, tp)
        if zeros:
            local = {n: np.zeros_like(a) for n, a in local.items()}
        h.register(local, layout=lay)
    return handles


class TestEndToEndReshard:
    @pytest.mark.parametrize("src_tp,dst_tp", [(4, 2), (2, 4), (2, 3)])
    def test_reshard_replicate_bytes_equal(self, src_tp, dst_tp):
        """A dest replica with a different TP degree completes replicate()
        with bit-identical reassembled tensors, striping interval reads
        across the source shards."""
        glob = model_tensors()
        hub = TensorHubClient(ReferenceServer())
        pubs = open_tp_group(hub, "pub", src_tp, glob)
        run_group(pubs, lambda h: h.publish(0))

        pulled = []
        orig = hub.transport.read_unit_range

        def spy(src_replica, src_shard, *a, **kw):
            pulled.append(src_shard)
            return orig(src_replica, src_shard, *a, **kw)

        hub.transport.read_unit_range = spy
        subs = open_tp_group(hub, "sub", dst_tp, glob, zeros=True)
        got = []
        run_group(subs, lambda h: got.append(h.replicate("latest")))
        assert got == [0] * dst_tp
        for h in subs:
            want, _ = tp_shard(glob, h.shard_idx, dst_tp)
            for n, arr in want.items():
                np.testing.assert_array_equal(h.store.get(n), arr)
        if src_tp > dst_tp:
            # scale-down: interval reads touched >= 2 distinct source shards
            assert len(set(pulled)) >= 2
        assert all(h.intervals_pulled > 0 for h in subs)

    def test_device_repack_path(self):
        """Device-gather repack produces the same bytes as the NumPy path."""
        glob = model_tensors(seed=3)
        hub = TensorHubClient(ReferenceServer())
        pubs = open_tp_group(hub, "pub", 4, glob)
        run_group(pubs, lambda h: h.publish(0))
        subs = open_tp_group(hub, "sub", 2, glob, zeros=True, device_repack=True)
        run_group(subs, lambda h: h.replicate(0))
        for h in subs:
            want, _ = tp_shard(glob, h.shard_idx, 2)
            for n, arr in want.items():
                np.testing.assert_array_equal(h.store.get(n), arr)

    def test_same_shard_count_different_axes_reshards(self):
        """Equal shard counts do NOT imply equal layouts: a dest sharded
        along a different axis than the source must take the reshard path
        (unit-for-unit copying would silently scramble weights)."""
        rng = np.random.default_rng(11)
        glob = {"w": rng.standard_normal((8, 8)).astype(np.float32)}
        hub = TensorHubClient(ReferenceServer())
        pubs = [hub.open("m", "rows", 4, i) for i in range(4)]
        for h in pubs:  # axis-0 sharding
            local, lay = tp_shard(glob, h.shard_idx, 4)
            h.register(local, layout=lay)
        run_group(pubs, lambda h: h.publish(0))
        subs = [hub.open("m", "cols", 4, i) for i in range(4)]
        for h in subs:  # axis-1 sharding, same shard count
            local, lay = tp_shard(glob, h.shard_idx, 4, axis_overrides={"w": 1})
            h.register({n: np.zeros_like(a) for n, a in local.items()}, layout=lay)
        run_group(subs, lambda h: h.replicate(0))
        for h in subs:
            want, _ = tp_shard(glob, h.shard_idx, 4, axis_overrides={"w": 1})
            np.testing.assert_array_equal(h.store.get("w"), want["w"])
        assert all(h.intervals_pulled > 0 for h in subs)  # reshard path ran

    def test_resharded_replica_serves_same_layout_reader(self):
        """A replica materialized via reshard serves a later same-layout
        reader through the plain unit pipe (its manifest family was
        registered at put_manifest time)."""
        glob = model_tensors(seed=5)
        hub = TensorHubClient(ReferenceServer())
        pubs = open_tp_group(hub, "pub", 4, glob)
        run_group(pubs, lambda h: h.publish(0))
        first = open_tp_group(hub, "r1", 2, glob, zeros=True)
        run_group(first, lambda h: h.replicate(0))
        second = open_tp_group(hub, "r2", 2, glob, zeros=True)
        run_group(second, lambda h: h.replicate(0))
        for h in second:
            want, _ = tp_shard(glob, h.shard_idx, 2)
            for n, arr in want.items():
                np.testing.assert_array_equal(h.store.get(n), arr)


# ---------------------------------------------------------------------------
# virtual time: failure re-planning + stall accounting
# ---------------------------------------------------------------------------


class TestSimReshard:
    def test_reshard_completes_and_stripes_bandwidth(self):
        units = [int(2e9)] * 4
        cl = SimCluster()
        tr = cl.add_replica("m", "tr0", 4, global_unit_bytes=units)
        ro = cl.add_replica("m", "ro0", 2, global_unit_bytes=units)
        tr.open()
        ro.open()
        cl.run()
        tr.publish(0)
        cl.run()
        ev = ro.replicate("latest")
        cl.run()
        assert ev.triggered and ev.error is None
        assert all(s.worker.total_stall > 0 for s in ro.shards)

    def test_source_death_mid_reshard_replans(self):
        """Kill the assigned source mid-reshard: the reader re-routes to a
        surviving replica with ANOTHER layout and still completes."""
        units = [int(2e9)] * 4
        cl = SimCluster()
        tr = cl.add_replica("m", "tr0", 4, global_unit_bytes=units)
        sa = cl.add_replica("m", "sa0", 2, global_unit_bytes=units)
        ro = cl.add_replica("m", "ro0", 8, global_unit_bytes=units)
        for r in (tr, sa, ro):
            r.open()
        cl.run()
        tr.publish(0)
        cl.run()
        sa.replicate("latest")
        cl.run()
        ev = ro.replicate("latest")
        cl.env.schedule(0.1, lambda: cl.kill_replica("tr0"))
        cl.run()
        assert ev.triggered and ev.error is None


# ---------------------------------------------------------------------------
# repack kernel parity
# ---------------------------------------------------------------------------


class TestRepackKernel:
    @settings(max_examples=20, deadline=None)
    @given(
        out_nbytes=st.integers(1, 8192),
        seed=st.integers(0, 10_000),
    )
    def test_kernel_matches_ref(self, out_nbytes, seed):
        from repro.kernels.repack import (
            random_instructions,
            repack_bytes,
            repack_ref,
        )

        rng = np.random.default_rng(seed)
        instrs = random_instructions(rng, out_nbytes)
        staging = rng.integers(
            0, 256, sum(n for _, _, n in instrs), dtype=np.uint8
        )
        got = np.asarray(repack_bytes(staging, instrs, out_nbytes))
        np.testing.assert_array_equal(got, repack_ref(staging, instrs, out_nbytes))

    @pytest.mark.parametrize("window", [777, 4096])
    def test_windowed_repack_matches_ref(self, monkeypatch, window):
        """An output longer than one gather window is gathered window by
        window, the last one padded; the bytes are the same."""
        from repro.kernels.repack import (
            ops,
            random_instructions,
            repack_bytes,
            repack_ref,
        )

        monkeypatch.setattr(ops, "GATHER_WINDOW", window)
        rng = np.random.default_rng(window)
        out_nbytes = 5000
        instrs = random_instructions(rng, out_nbytes)
        staging = rng.integers(
            0, 256, sum(n for _, _, n in instrs), dtype=np.uint8
        )
        got = repack_bytes(staging, instrs, out_nbytes)
        np.testing.assert_array_equal(got, repack_ref(staging, instrs, out_nbytes))

    def test_gather_ref_matches_kernel(self):
        from repro.kernels.repack import gather_bytes

        rng = np.random.default_rng(0)
        staging = rng.integers(0, 256, 1024, dtype=np.uint8)
        idx = rng.integers(0, 1024, 3000, dtype=np.int32)
        np.testing.assert_array_equal(
            np.asarray(gather_bytes(staging, idx)), staging[idx]
        )

    def test_executor_kernel_vs_numpy(self):
        """Full executor repack: kernel path == NumPy path on a real plan."""
        from repro.resharding import ReshardExecutor

        glob = model_tensors(seed=7)
        src = layouts_for(glob, 4)
        dst = layouts_for(glob, 2)
        local, lay = tp_shard(glob, 0, 2)
        manifest = manifest_for(local, lay)
        plan = plan_shard(src, dst, 0, stripe_min=16, num_dest_units=manifest.num_units)
        ex_np = ReshardExecutor(plan, manifest, use_kernel=False)
        ex_k = ReshardExecutor(plan, manifest, use_kernel=True)
        rng = np.random.default_rng(1)
        for unit, placed in ex_np.unit_batches():
            staging = rng.integers(
                0, 256, ex_np.staging_bytes(unit.index), dtype=np.uint8
            )
            np.testing.assert_array_equal(
                ex_np.repack(unit.index, staging), ex_k.repack(unit.index, staging)
            )
