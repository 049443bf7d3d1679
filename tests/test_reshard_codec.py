"""Codec-capable resharding: row-grid planning properties, fused
dequant+repack parity, cross-transport byte identity, end-to-end int8
reshard through the threaded client, and data-plane connection pooling.

The tentpole contract under test: a cross-DC pull between mismatched
shard layouts carries the negotiated wire codec end to end — the planner
widens interval reads to the quantization row grid, the transport ships
undecoded wire frames, and the fused dequant+gather path writes repacked
rows directly — while a raw plan stays bit-exact with the pre-codec
planner (zero widening).
"""

import threading

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import ReferenceServer, TensorHubClient
from repro.core.meta import WorkerInfo
from repro.resharding import (
    ReshardExecutor,
    layout_from_manifests,
    plan_shard,
    rowgrid,
    tp_shard,
)
from repro.transfer.codec import Int8Codec, get_codec, parse_int8_frame
from repro.transfer.engine import LocalTransport, WorkerRegistry, WorkerStore
from repro.transfer.simcluster import make_layout_manifests

RB = Int8Codec().row_bytes("float32")  # 256 elems * 4 B


# ---------------------------------------------------------------------------
# row-grid helpers: pure alignment properties
# ---------------------------------------------------------------------------


class TestRowGrid:
    @settings(max_examples=50, deadline=None)
    @given(n=st.integers(0, 1 << 20), a=st.sampled_from([1, 2, 512, RB]))
    def test_chunk_align_properties(self, n, a):
        out = rowgrid.chunk_align(n, a)
        assert out >= n and out % a == 0 and out - n < max(a, 1)

    @settings(max_examples=100, deadline=None)
    @given(
        offset=st.integers(0, 1 << 18),
        nbytes=st.integers(1, 1 << 16),
        rb=st.sampled_from([256 * 2, RB, 256 * 8]),
        slack=st.integers(0, 4 * RB),
    )
    def test_snap_satisfies_read_contract(self, offset, nbytes, rb, slack):
        """The widened range starts on the row grid and stops either on
        it or exactly at the unit end — precisely the alignment
        ``read_unit_range`` enforces for coded reads."""
        unit_nbytes = offset + nbytes + slack
        lead, tail = rowgrid.snap(offset, nbytes, rb, unit_nbytes)
        start = offset - lead
        stop = offset + nbytes + tail
        assert 0 <= lead < rb and tail >= 0
        assert start % rb == 0
        assert stop % rb == 0 or stop == unit_nbytes
        assert stop <= unit_nbytes

    def test_row_granularity_is_max_over_codecs(self):
        assert rowgrid.row_granularity(["raw"], "float32") == 1
        assert rowgrid.row_granularity(["raw", "int8"], "float32") == RB
        assert rowgrid.row_granularity(["int8"], "bfloat16") == 256 * 2


# ---------------------------------------------------------------------------
# planner: codec-aware plans tile exactly and stay within source bounds
# ---------------------------------------------------------------------------


def _layouts(sizes, src_tp, dst_tp, dtype="float32"):
    src = layout_from_manifests(
        dict(enumerate(make_layout_manifests(sizes, src_tp, dtype=dtype))),
        src_tp,
    )
    dst_manifests = make_layout_manifests(sizes, dst_tp, dtype=dtype)
    dst = layout_from_manifests(dict(enumerate(dst_manifests)), dst_tp)
    return src, dst, dst_manifests


class TestCodecPlans:
    @settings(max_examples=25, deadline=None)
    @given(
        src_tp=st.sampled_from([1, 2, 3, 4, 8]),
        dst_tp=st.sampled_from([1, 2, 4]),
        sizes=st.lists(
            st.integers(RB // 4, 1 << 18), min_size=1, max_size=4
        ),
    )
    def test_int8_plan_row_aligned_and_tiles(self, src_tp, dst_tp, sizes):
        """Property sweep: every interval of an int8 plan reads a
        row-grid-aligned range that stays inside its source unit (the
        per-source ceiling), and the un-widened payloads still tile each
        destination unit exactly."""
        sizes = [s * 4 for s in sizes]  # element-aligned float32 tensors
        src, dst, dst_manifests = _layouts(sizes, src_tp, dst_tp)
        for shard in range(dst_tp):
            plan = plan_shard(
                src, dst, shard,
                num_dest_units=dst_manifests[shard].num_units,
                codec="int8",
            )
            covered = {u.index: 0 for u in dst_manifests[shard].units}
            for iv in plan.intervals:
                rb = RB  # all-f32 layouts
                start = iv.read_offset
                stop = start + iv.read_nbytes
                assert start >= 0 and start % rb == 0, iv
                assert stop % rb == 0 or stop == iv.src_unit_nbytes, iv
                assert stop <= iv.src_unit_nbytes, iv
                assert iv.read_nbytes == iv.lead + iv.nbytes + iv.tail
                covered[iv.dest_unit] += iv.nbytes
            for u in dst_manifests[shard].units:
                assert covered[u.index] == u.nbytes, (shard, u.index)

    def test_raw_plan_has_zero_widening(self):
        """A raw plan is bit-compatible with the pre-codec planner: no
        row-grid widening anywhere (wire bytes == payload bytes)."""
        src, dst, dst_manifests = _layouts([1 << 20] * 3, 4, 2)
        for shard in range(2):
            plan = plan_shard(
                src, dst, shard,
                num_dest_units=dst_manifests[shard].num_units,
                codec="raw",
            )
            for iv in plan.intervals:
                assert iv.lead == 0 and iv.tail == 0
                assert iv.read_nbytes == iv.nbytes


# ---------------------------------------------------------------------------
# fused dequant+repack parity
# ---------------------------------------------------------------------------


def _frames(rng, specs):
    """Encode per-spec float32 payloads; return (parsed frames, wires)."""
    frames, wires = [], []
    for n_elems in specs:
        x = (rng.standard_normal(n_elems) * 2).astype(np.float32)
        wire = get_codec("int8").encode(x.view(np.uint8).reshape(-1), "float32")
        frames.append(parse_int8_frame(wire))
        wires.append(wire)
    return frames, wires


class TestFusedParity:
    @pytest.mark.parametrize("device_kernel", [False, True])
    def test_fused_matches_staged_decode(self, device_kernel):
        """Fused placement decode (numpy + device kernel) is
        bit-identical to decode-whole-frame-then-trim — including
        lead/tail trimming and a passthrough overlay."""
        from repro.kernels.quant import fused_repack, fused_repack_np

        rng = np.random.default_rng(5)
        frames, wires = _frames(rng, [1024, 2048, 512])
        c = get_codec("int8")
        # (frame, lead, nbytes, unit_offset): trim rows off frame 1, and
        # make frame 2 a passthrough (non-finite payload)
        bad = np.full(256, np.inf, np.float32)
        pw = c.encode(bad.view(np.uint8).reshape(-1), "float32")
        pf = parse_int8_frame(pw)
        assert pf.is_passthrough
        placements = [
            (frames[0], 0, 4096, 0),
            (frames[1], RB, 4096, 4096),  # lead-trimmed by one row
            (pf, 4, 1000, 8192),  # passthrough overlay, odd offsets
        ]
        out_nbytes = 4096 + 4096 + 1024
        want = np.zeros(out_nbytes, np.uint8)
        want[0:4096] = c.decode(wires[0])[0:4096]
        want[4096:8192] = c.decode(wires[1])[RB : RB + 4096]
        want[8192 : 8192 + 1000] = bad.view(np.uint8)[4 : 4 + 1000]
        got_np = fused_repack_np(placements, out_nbytes)
        assert np.array_equal(got_np, want)
        if device_kernel:
            got_k = fused_repack(placements, out_nbytes)
            assert np.array_equal(got_k, want)

    @pytest.mark.parametrize("window", [1000, 2048])
    def test_windowed_fused_decode_matches_numpy(self, monkeypatch, window):
        """A unit longer than one gather window decodes window by window
        (the last one padded) to the NumPy fusion's bytes, gaps included."""
        from repro.kernels.quant import fused_repack, fused_repack_np
        from repro.kernels.repack import ops

        monkeypatch.setattr(ops, "GATHER_WINDOW", window)
        frames, _ = _frames(np.random.default_rng(window), [1024, 2048, 512])
        placements = [
            (frames[0], 0, 4096, 0),
            (frames[1], RB, 4096, 4096 + 512),  # a 512-byte gap before it
            (frames[2], 4, 1000, 9216),  # part of one row
        ]
        out_nbytes = 9216 + 1000
        want = fused_repack_np(placements, out_nbytes)
        assert np.array_equal(fused_repack(placements, out_nbytes), want)

    def test_executor_fused_repack_matches_staged(self):
        """ReshardExecutor.fused_repack over a real plan's wire frames ==
        staged decode into staging + repack."""
        sizes = [RB * 64, RB * 40]
        src, dst, dst_manifests = _layouts(sizes, 4, 2)
        rng = np.random.default_rng(9)
        # materialize the source shards' unit payloads
        src_payloads = {}
        src_manifests = make_layout_manifests(sizes, 4, dtype="float32")
        for s, m in enumerate(src_manifests):
            for u in m.units:
                src_payloads[(s, u.index)] = (
                    (rng.standard_normal(u.nbytes // 4) * 2)
                    .astype(np.float32).view(np.uint8).reshape(-1)
                )
        c = get_codec("int8")
        for shard in range(2):
            plan = plan_shard(
                src, dst, shard,
                num_dest_units=dst_manifests[shard].num_units,
                codec="int8",
            )
            ex = ReshardExecutor(plan, dst_manifests[shard])
            for unit, placed in ex.unit_batches():
                frames, staging = [], ex.make_staging(unit.index)
                for p in placed:
                    iv = p.interval
                    payload = src_payloads[(iv.source_shard, iv.source_unit)]
                    wire = c.encode(
                        payload[iv.read_offset : iv.read_offset + iv.read_nbytes],
                        "float32",
                    )
                    frames.append(wire)
                    staging[
                        p.staging_offset : p.staging_offset + iv.nbytes
                    ] = c.decode(wire)[iv.lead : iv.lead + iv.nbytes]
                fused = ex.fused_repack(unit.index, frames)
                staged = ex.repack(unit.index, staging)
                assert np.array_equal(fused, staged), (shard, unit.index)


# ---------------------------------------------------------------------------
# threaded client end to end: negotiated int8 over a resharded pull
# ---------------------------------------------------------------------------


def _model_tensors(seed=0):
    """Row-grid-friendly model: every TP-{1,2,4} slice is a whole number
    of 256-element quantization rows, so the resharded decode is
    comparable bit-for-bit against a same-layout int8 pull."""
    rng = np.random.default_rng(seed)
    return {
        "w0": rng.standard_normal((4096, 8)).astype(np.float32),
        "w1": rng.standard_normal((2048, 4)).astype(np.float32),
    }


def _run_group(handles, fn):
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


def _open_tp_group(hub, name, tp, glob, *, zeros=False, dc="dc0"):
    handles = [hub.open("m", name, tp, i, datacenter=dc) for i in range(tp)]
    for h in handles:
        local, lay = tp_shard(glob, h.shard_idx, tp)
        if zeros:
            local = {n: np.zeros_like(a) for n, a in local.items()}
        h.register(local, layout=lay)
    return handles


def _reshard_pull(src_tp, dst_tp, *, wan_codec="int8", seed=0):
    """Publish at ``src_tp`` in dc0, reshard-replicate at ``dst_tp`` in
    dc1; returns (sub handles, wire bytes moved)."""
    glob = _model_tensors(seed)
    hub = TensorHubClient(ReferenceServer(wan_codec=wan_codec))
    pubs = _open_tp_group(hub, "pub", src_tp, glob, dc="dc0")
    _run_group(pubs, lambda h: h.publish(0))
    before = hub.transport.bytes_moved
    subs = _open_tp_group(hub, "sub", dst_tp, glob, zeros=True, dc="dc1")
    _run_group(subs, lambda h: h.replicate(0))
    return glob, subs, hub.transport.bytes_moved - before


class TestEndToEndInt8Reshard:
    def test_cross_dc_reshard_carries_int8_and_shrinks_wire(self):
        """Acceptance: a cross-DC TP-mismatched pull carries int8 end to
        end — values match the int8 round-trip, wire bytes >= 3.5x
        smaller than the same pull forced raw."""
        glob, subs, coded_bytes = _reshard_pull(4, 2)
        for h in subs:
            want, _ = tp_shard(glob, h.shard_idx, 2)
            for n, arr in want.items():
                got = h.store.get(n)
                # lossy codec: close values, not identical bits
                assert np.allclose(got, arr, rtol=0.02, atol=0.02), n
                assert not np.array_equal(got, arr)
        _, _, raw_bytes = _reshard_pull(4, 2, wan_codec="raw")
        assert raw_bytes / coded_bytes >= 3.5

    @pytest.mark.parametrize("src_tp,dst_tp", [(4, 2), (2, 4)])
    def test_decoded_bytes_match_same_layout_int8_pull(self, src_tp, dst_tp):
        """Acceptance: the resharded int8 decode is bit-identical to a
        same-layout int8 pull of the same weights (row-aligned shard
        splits share the quantization grid, so per-row scales agree)."""
        glob, resharded, _ = _reshard_pull(src_tp, dst_tp)
        glob2, same_layout, _ = _reshard_pull(dst_tp, dst_tp)
        for ha, hb in zip(resharded, same_layout):
            for n in glob:
                assert np.array_equal(
                    ha.store.get(n).view(np.uint8),
                    hb.store.get(n).view(np.uint8),
                ), (n, ha.shard_idx)

    def test_raw_reshard_stays_bit_exact(self):
        """Forced-raw reshard is byte-identical to the publisher (the
        pre-refactor wire behavior)."""
        glob, subs, _ = _reshard_pull(4, 2, wan_codec="raw")
        for h in subs:
            want, _ = tp_shard(glob, h.shard_idx, 2)
            for n, arr in want.items():
                np.testing.assert_array_equal(h.store.get(n), arr)

    def test_fused_kernel_path_matches_numpy_path(self):
        """device_repack=True routes the resharded decode through the
        fused device kernel — same bytes as the NumPy fusion."""
        from repro import obs
        from repro.obs import telemetry

        glob = _model_tensors()
        rec = obs.Recorder()
        hub = TensorHubClient(ReferenceServer(), recorder=rec)
        pubs = _open_tp_group(hub, "pub", 4, glob, dc="dc0")
        _run_group(pubs, lambda h: h.publish(0))
        subs_np = _open_tp_group(hub, "np", 2, glob, zeros=True, dc="dc1")
        _run_group(subs_np, lambda h: h.replicate(0))
        host_units = rec.counter(telemetry.CTR_DECODE_HOST_UNITS)
        assert host_units > 0
        assert rec.counter(telemetry.CTR_DECODE_KERNEL_UNITS) == 0
        # closed, so the kernel replica cannot copy its same-layout bytes
        # and must reshard from the publisher
        for h in subs_np:
            h.close()
        subs_k = [
            hub.open("m", "kern", 2, i, datacenter="dc1", device_repack=True)
            for i in range(2)
        ]
        for h in subs_k:
            local, lay = tp_shard(glob, h.shard_idx, 2)
            h.register(
                {n: np.zeros_like(a) for n, a in local.items()}, layout=lay
            )
        _run_group(subs_k, lambda h: h.replicate(0))
        # every unit of the kernel pull decoded on the device, none on host
        assert rec.counter(telemetry.CTR_DECODE_KERNEL_UNITS) > 0
        assert rec.counter(telemetry.CTR_DECODE_HOST_UNITS) == host_units
        for ha, hb in zip(subs_k, subs_np):
            for n in glob:
                assert np.array_equal(
                    ha.store.get(n).view(np.uint8),
                    hb.store.get(n).view(np.uint8),
                ), n


# ---------------------------------------------------------------------------
# negotiation scope: degrade only for genuinely unalignable plans
# ---------------------------------------------------------------------------


class TestDegradeScope:
    def _server_with_reshard(self, dtype):
        s = ReferenceServer()
        manifests = make_layout_manifests([1 << 20] * 4, 2, dtype=dtype)
        for i in range(2):
            s.open(
                "m", "pub", 2, i,
                worker=WorkerInfo(f"pub/s{i}", "dc0/pub", "dc0"),
            )
            s.register("m", "pub", i)
            s.publish("m", "pub", i, 0, manifests[i], op_id=0)
        s.open("m", "r", 1, 0, worker=WorkerInfo("r/s0", "dc1/r", "dc1"))
        s.register("m", "r", 0)
        return s

    def test_quantizable_reshard_negotiates_int8_no_degrade(self):
        s = self._server_with_reshard("float32")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.resharded and a.codec == "int8"
        assert s.stats["codec_degrades"] == 0

    def test_unquantizable_reshard_degrades_and_counts(self):
        """codec_degrades ticks ONLY for genuinely unalignable payloads:
        every source tensor non-quantizable (uint8) -> raw + one tick."""
        s = self._server_with_reshard("uint8")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.resharded and a.codec == "raw"
        assert s.stats["codec_degrades"] == 1


# ---------------------------------------------------------------------------
# remote transport: cross-transport byte identity + connection pooling
# ---------------------------------------------------------------------------


class TestRemoteDataPlane:
    def _served_source(self):
        from repro.net.data import RemoteTransport, WorkerDataServer

        rng = np.random.default_rng(3)
        x = (rng.standard_normal(RB * 16 // 4) * 2).astype(np.float32)
        src_reg = WorkerRegistry()
        st_ = WorkerStore("src/shard0")
        st_.register({"t": x})
        src_reg.add("src", 0, st_)
        server = WorkerDataServer(src_reg).start()
        remote = RemoteTransport(
            WorkerRegistry(), lambda *_: server.address
        )
        local = LocalTransport(src_reg)
        return server, remote, local, st_

    def test_wire_frames_identical_across_transports(self):
        """The networked data plane returns byte-identical int8 wire
        frames (and decoded ranges) to the in-process transport."""
        server, remote, local, st_ = self._served_source()
        try:
            unit = st_.units[0]
            for decode in (True, False):
                for off, n in [(0, unit.nbytes), (RB, 4 * RB)]:
                    a = local.read_unit_range(
                        "src", 0, unit, off, n, codec="int8", decode=decode
                    )
                    b = remote.read_unit_range(
                        "src", 0, unit, off, n, codec="int8", decode=decode
                    )
                    assert np.array_equal(a, b), (decode, off, n)
        finally:
            remote.close_pool()
            server.shutdown()

    def test_connection_pool_reuses_sockets(self):
        """Satellite: per-(host, port) keep-alive pooling — a windowed
        pull's worth of reads opens O(pool) sockets, not O(reads)."""
        server, remote, local, st_ = self._served_source()
        try:
            unit = st_.units[0]
            for _ in range(10):
                remote.read_unit_range("src", 0, unit, 0, RB, codec="int8")
            assert remote.conn_opens <= 2
            assert remote.conn_reuses >= 8
            assert remote.conn_opens + remote.conn_reuses >= 10
        finally:
            remote.close_pool()
            server.shutdown()
