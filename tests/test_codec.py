"""Wire-codec subsystem tests: int8 round-trips across the model-zoo
dtypes, wire-format integrity, chunk/row alignment, server-side per-link
negotiation, both data planes (threaded bytes + fluid sim), and the
``codec="raw"`` bit-identity guarantee."""

import math
import threading

import numpy as np
import pytest

from repro.core import ReferenceServer, TensorHubClient
from repro.core.errors import TensorHubError
from repro.core.meta import WorkerInfo
from repro.core.oplog import OpLog
from repro.transfer.codec import (
    CodecError,
    DeltaCodec,
    FixedRatioCodec,
    Int8Codec,
    StaleBaseError,
    get_codec,
    unit_wire_dtype,
    wire_ratio,
)
from repro.transfer.engine import (
    LocalTransport,
    TransportError,
    WorkerRegistry,
    WorkerStore,
)


def _np_dtype(name):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _rand_bytes(dtype: str, n: int, seed=0, scale=3.0) -> np.ndarray:
    x = (np.random.RandomState(seed).randn(n) * scale).astype(_np_dtype(dtype))
    return np.ascontiguousarray(x).view(np.uint8).reshape(-1)


def _rel_err(decoded: np.ndarray, original: np.ndarray, dtype: str) -> float:
    a = decoded.view(_np_dtype(dtype)).astype(np.float32)
    b = original.view(_np_dtype(dtype)).astype(np.float32)
    denom = max(float(np.max(np.abs(b))), 1e-12)
    return float(np.max(np.abs(a - b))) / denom


class TestInt8Wire:
    """Pure codec: framing, round-trips, integrity."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16", "float64"])
    @pytest.mark.parametrize("n", [1, 255, 256, 1000, 4096, 100001])
    def test_roundtrip(self, dtype, n):
        c = get_codec("int8")
        payload = _rand_bytes(dtype, n, seed=n)
        wire = c.encode(payload, dtype)
        assert wire.nbytes == c.wire_nbytes(payload.nbytes, dtype)
        decoded = c.decode(wire)
        assert decoded.nbytes == payload.nbytes
        assert _rel_err(decoded, payload, dtype) < 0.01

    def test_all_zero_rows_exact(self):
        c = get_codec("int8")
        payload = np.zeros(3000, np.float32).view(np.uint8).reshape(-1)
        assert np.array_equal(c.decode(c.encode(payload, "float32")), payload)

    def test_extreme_value_rows(self):
        c = get_codec("int8")
        x = np.full(1000, 3.0e38, np.float32)
        x[::7] = -3.0e38
        payload = x.view(np.uint8).reshape(-1)
        decoded = c.decode(c.encode(payload, "float32"))
        assert _rel_err(decoded, payload, "float32") < 0.01

    def test_non_finite_weights_passthrough_bit_exact(self):
        """Transient NaN/Inf weights (RL loss spikes) must not brick the
        cross-DC transfer: encode falls back to the tagged bit-exact
        passthrough instead of producing non-finite scales."""
        c = get_codec("int8")
        for poison in (np.nan, np.inf, -np.inf):
            x = np.random.RandomState(0).randn(1000).astype(np.float32)
            x[137] = poison
            payload = x.view(np.uint8).reshape(-1)
            wire = c.encode(payload, "float32")
            assert np.array_equal(c.decode(wire), payload)
        # f64 values that overflow the f32 quantization grid too
        big = np.full(300, 1e308, np.float64).view(np.uint8).reshape(-1)
        assert np.array_equal(c.decode(c.encode(big, "float64")), big)

    def test_non_float_passthrough_bit_exact(self):
        c = get_codec("int8")
        payload = np.arange(999, dtype=np.int32).view(np.uint8).reshape(-1)
        wire = c.encode(payload, "int32")
        assert np.array_equal(c.decode(wire), payload)

    def test_unknown_dtype_passthrough(self):
        c = get_codec("int8")
        payload = np.frombuffer(b"hello world!", np.uint8)
        assert np.array_equal(c.decode(c.encode(payload, None)), payload)

    def test_wire_smaller_than_payload(self):
        """The headline ratios: ~0.2539x of f32 bytes (~3.9x reduction),
        ~0.5078x of bf16 (~2.0x) at per-256 f32 scales."""
        c = get_codec("int8")
        r32 = wire_ratio(c, [4 << 20] * 8, "float32")
        r16 = wire_ratio(c, [4 << 20] * 8, "bfloat16")
        assert math.isclose(r32, (1 + 4 / 256) / 4, rel_tol=1e-3)
        assert math.isclose(r16, (1 + 4 / 256) / 2, rel_tol=1e-3)
        assert 3.8 < 1 / r32 < 4.0
        assert 1.9 < 1 / r16 < 2.1

    def test_truncated_wire_rejected(self):
        c = get_codec("int8")
        wire = c.encode(_rand_bytes("float32", 1000), "float32")
        with pytest.raises(CodecError):
            c.decode(wire[:-3])
        with pytest.raises(CodecError):
            c.decode(wire[:4])

    def test_bad_magic_rejected(self):
        c = get_codec("int8")
        wire = c.encode(_rand_bytes("float32", 1000), "float32").copy()
        wire[:4] = 0
        with pytest.raises(CodecError):
            c.decode(wire)

    def test_corrupt_scales_rejected(self):
        """Scale integrity: a NaN/inf scale fails the wire-level check."""
        c = get_codec("int8")
        wire = c.encode(_rand_bytes("float32", 1000), "float32").copy()
        wire[20:24] = np.frombuffer(
            np.float32(np.nan).tobytes(), np.uint8
        )  # first scale word
        with pytest.raises(CodecError):
            c.decode(wire)

    def test_chunk_rows_match_whole_unit(self):
        """Row-aligned sub-range encodes produce exactly the rows of the
        whole-payload encoding — chunked units reassemble bit-identically
        to an unchunked transfer."""
        c = get_codec("int8")
        payload = _rand_bytes("float32", 50000, seed=7)
        full = c.decode(c.encode(payload, "float32"))
        rb = c.row_bytes("float32")
        for per in (rb, 3 * rb, 17 * rb):
            parts, off = [], 0
            while off < payload.nbytes:
                step = min(per, payload.nbytes - off)
                parts.append(c.decode(c.encode(payload[off : off + step], "float32")))
                off += step
            assert np.array_equal(np.concatenate(parts), full)

    def test_backends_agree(self):
        """kernels/quant-backed path vs the pure-NumPy reference: same
        scheme, same rounding, so the same wire bytes and decodes."""
        payload = _rand_bytes("float32", 12345, seed=3)
        cn, cj = Int8Codec(backend="numpy"), Int8Codec(backend="jax")
        assert np.array_equal(
            cn.encode(payload, "float32"), cj.encode(payload, "float32")
        )
        dn = cn.decode(cn.encode(payload, "float32"))
        dj = cj.decode(cj.encode(payload, "float32"))
        assert _rel_err(dn, payload, "float32") < 0.01
        assert _rel_err(dj, payload, "float32") < 0.01
        assert _rel_err(dn, dj, "float32") < 1e-3

    def test_numpy_matches_pallas_kernel(self):
        """The NumPy fallback quantizes exactly like the Pallas kernel
        (interpret mode): same q, scales to 1 ulp."""
        jax = pytest.importorskip("jax")
        from repro.kernels.quant.kernel import quantize_rows

        rows = (np.random.RandomState(5).randn(8, 256) * 2).astype(np.float32)
        qk, sk = quantize_rows(jax.numpy.asarray(rows), interpret=True)
        c = Int8Codec(backend="numpy")
        qn, sn = c._quant_rows(rows)
        assert np.max(np.abs(qn.astype(np.int32) - np.asarray(qk, np.int32))) <= 1
        np.testing.assert_allclose(sn, np.asarray(sk), rtol=1e-6)

    def test_registry(self):
        assert get_codec("raw").name == "raw"
        assert get_codec("int8").name == "int8"
        fixed = get_codec("fixed:0.25")
        assert isinstance(fixed, FixedRatioCodec) and fixed.ratio == 0.25
        with pytest.raises(TensorHubError):
            get_codec("zstd")
        with pytest.raises(TensorHubError):
            get_codec("fixed:nope")

    def test_fixed_ratio_is_sim_only(self):
        fixed = get_codec("fixed:0.5")
        with pytest.raises(CodecError):
            fixed.encode(np.zeros(8, np.uint8), "float32")
        with pytest.raises(CodecError):
            fixed.decode(np.zeros(8, np.uint8))

    def test_raw_is_identity(self):
        raw = get_codec("raw")
        payload = _rand_bytes("bfloat16", 777)
        assert raw.encode(payload, "bfloat16") is payload
        assert raw.decode(payload) is payload
        assert raw.wire_nbytes(123, None) == 123


class TestDeltaWire:
    """delta:<base> framing: residual round-trips against a held base,
    stale-base detection, fallback frames, wire sizing."""

    def _versions(self, dtype, n, changed_frac=0.25, seed=5):
        """Correlated (base, payload) pair: ``changed_frac`` of the quant
        rows differ, the rest are bit-identical. ``held`` is what an
        int8-seeded destination actually holds for the base version."""
        base = _rand_bytes(dtype, n, seed=seed)
        npd = _np_dtype(dtype)
        x = base.view(npd).astype(np.float32)
        rows = -(-n // 256)
        k = int(rows * changed_frac)
        y = x.copy()
        if k:
            y[: k * 256] = y[: k * 256] * 1.001 + 0.01
        payload = np.ascontiguousarray(y.astype(npd)).view(np.uint8).reshape(-1)
        i8 = get_codec("int8")
        held = i8.decode(i8.encode(base, dtype))
        return base, payload, held

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("n", [1000, 256 * 40 + 17, 100001])
    def test_roundtrip_changed_rows(self, dtype, n):
        c = get_codec("delta:int8")
        base, payload, held = self._versions(dtype, n)
        wire = c.encode(payload, dtype, base=base)
        out = c.decode(wire, base=held)
        assert out.nbytes == payload.nbytes
        assert _rel_err(out, payload, dtype) < 0.01
        # the headline property: fewer wire bytes than a plain int8 frame
        assert wire.nbytes < get_codec("int8").wire_nbytes(payload.nbytes, dtype)

    def test_skipped_rows_bit_exact_vs_int8_baseline(self):
        """An unchanged row decodes to exactly the destination's held
        bytes — which (int8 round-trip being idempotent) are exactly what
        a fresh int8 pull of the new version would have delivered."""
        c = get_codec("delta:int8")
        i8 = get_codec("int8")
        n = 256 * 64
        base, payload, held = self._versions("float32", n, changed_frac=0.25)
        out = c.decode(c.encode(payload, "float32", base=base), base=held)
        baseline = i8.decode(i8.encode(payload, "float32"))
        cut = (256 * 16) * 4  # first quarter of rows changed
        assert np.array_equal(out[cut:], held[cut:])
        assert np.array_equal(out[cut:], baseline[cut:])

    def test_error_no_worse_than_int8(self):
        c = get_codec("delta:int8")
        i8 = get_codec("int8")
        base, payload, held = self._versions("float32", 256 * 64)
        out = c.decode(c.encode(payload, "float32", base=base), base=held)
        baseline = i8.decode(i8.encode(payload, "float32"))
        assert _rel_err(out, payload, "float32") <= (
            _rel_err(baseline, payload, "float32") + 1e-6
        )

    def test_identical_versions_ship_bitmap_only(self):
        c = get_codec("delta:int8")
        base, _, held = self._versions("float32", 256 * 64, changed_frac=0.0)
        wire = c.encode(base, "float32", base=base)
        assert wire.nbytes == c.wire_nbytes_at(base.nbytes, "float32", 0.0)
        assert wire.nbytes < 0.01 * get_codec("int8").wire_nbytes(
            base.nbytes, "float32"
        )
        assert np.array_equal(c.decode(wire, base=held), held)

    def test_zero_residual_rows_skipped(self):
        """A row whose bits changed but that lands exactly on the bytes
        the destination already holds (zero residual) still ships as a
        single bitmap bit."""
        c = get_codec("delta:int8")
        i8 = get_codec("int8")
        base = _rand_bytes("float32", 256 * 8, seed=3)
        held = i8.decode(i8.encode(base, "float32"))
        payload = base.copy()
        payload[: 256 * 4] = held[: 256 * 4]  # row 0 moved onto the quant grid
        wire = c.encode(payload, "float32", base=base)
        assert wire.nbytes == c.wire_nbytes_at(base.nbytes, "float32", 0.0)
        assert np.array_equal(c.decode(wire, base=held), held)

    def test_non_finite_payload_falls_back_bit_exact(self):
        c = get_codec("delta:int8")
        base, payload, _ = self._versions("float32", 1000)
        poisoned = payload.view(np.float32).copy()
        poisoned[137] = np.nan
        pb = poisoned.view(np.uint8).reshape(-1)
        wire = c.encode(pb, "float32", base=base)
        # fallback frames decode without a base (int8 passthrough)
        assert np.array_equal(c.decode(wire), pb)

    def test_no_base_encode_falls_back(self):
        ci = get_codec("delta:int8")
        i8 = get_codec("int8")
        base, payload, _ = self._versions("float32", 1000)
        wire = ci.encode(payload, "float32")  # destination is fresh
        assert np.array_equal(ci.decode(wire), i8.decode(i8.encode(payload, "float32")))
        # a raw-based delta must keep raw's bit-identity guarantee
        cr = get_codec("delta:raw")
        wire = cr.encode(payload, "float32")
        assert np.array_equal(cr.decode(wire), payload)

    def test_delta_raw_roundtrip(self):
        c = get_codec("delta:raw")
        base, payload, _ = self._versions("float32", 256 * 40 + 17)
        wire = c.encode(payload, "float32", base=base)
        out = c.decode(wire, base=base)  # raw destination holds exact bytes
        assert wire.nbytes < payload.nbytes
        assert _rel_err(out, payload, "float32") < 0.01
        cut = (-(-(256 * 40 + 17) // 256) // 4) * 256 * 4
        assert np.array_equal(out[cut:], base[cut:])

    def test_stale_base_rejected(self):
        c = get_codec("delta:int8")
        base, payload, held = self._versions("float32", 256 * 16)
        wire = c.encode(payload, "float32", base=base)
        with pytest.raises(StaleBaseError):
            c.decode(wire)  # base evicted
        with pytest.raises(StaleBaseError):
            c.decode(wire, base=held[:-4])  # wrong size
        with pytest.raises(StaleBaseError):
            c.decode(wire, base=np.zeros_like(held))  # digest mismatch
        # StaleBaseError is a CodecError: undistinguishing callers degrade
        assert issubclass(StaleBaseError, CodecError)

    def test_truncated_delta_frame_not_stale(self):
        """A torn frame with a perfectly good base is wire corruption
        (corrupt evidence, quarantine), never a stale-base fallback."""
        c = get_codec("delta:int8")
        base, payload, held = self._versions("float32", 256 * 16)
        wire = c.encode(payload, "float32", base=base)
        for cut in (wire.nbytes - 3, 20, 7):
            with pytest.raises(CodecError) as ei:
                c.decode(wire[:cut], base=held)
            assert not isinstance(ei.value, StaleBaseError)

    def test_chunked_delta_rows_match_whole(self):
        """Row-aligned sub-range encodes (the chunked-unit path) decode to
        exactly the rows of the whole-payload encoding."""
        c = get_codec("delta:int8")
        i8 = get_codec("int8")
        base, payload, _ = self._versions("float32", 256 * 52)
        held = i8.decode(i8.encode(base, "float32"))
        whole = c.decode(c.encode(payload, "float32", base=base), base=held)
        rb = c.row_bytes("float32")
        for per in (rb, 13 * rb):
            parts, off = [], 0
            while off < payload.nbytes:
                step = min(per, payload.nbytes - off)
                w = c.encode(payload[off : off + step], "float32", base=base[off : off + step])
                parts.append(c.decode(w, base=held[off : off + step]))
                off += step
            assert np.array_equal(np.concatenate(parts), whole)

    def test_registry_and_attrs(self):
        c = get_codec("delta:int8")
        assert isinstance(c, DeltaCodec) and c.name == "delta:int8"
        assert c.needs_base and not c.lossless
        assert c.row_bytes("float32") == get_codec("int8").row_bytes("float32")
        assert get_codec("delta:raw").name == "delta:raw"
        assert not get_codec("int8").needs_base  # baseless codecs unchanged
        with pytest.raises(TensorHubError):
            get_codec("delta:fixed:0.5")
        with pytest.raises(TensorHubError):
            get_codec("delta:nope")

    def test_wire_sizing_model(self):
        c = get_codec("delta:int8")
        n = 4 << 20
        sizes = [c.wire_nbytes_at(n, "float32", f) for f in (0.0, 0.25, 0.5, 1.0)]
        assert sizes == sorted(sizes)
        i8 = get_codec("int8").wire_nbytes(n, "float32")
        assert sizes[1] < 0.3 * i8  # 25% changed rows -> ~4x fewer bytes
        assert sizes[3] >= i8  # all rows kept: digest+bitmap overhead
        assert c.wire_nbytes(n, "float32") == sizes[3]
        # the sim's per-manifest ratio follows the same model
        r_delta = wire_ratio(c, [n] * 4, "float32", delta_kept_frac=0.25)
        r_int8 = wire_ratio(get_codec("int8"), [n] * 4, "float32")
        assert r_delta < 0.3 * r_int8
        # non-quantizable payloads ride as tagged passthrough
        assert c.wire_nbytes_at(999, None, 0.25) == 999 + 20


class TestQuantOpsWireBytes:
    """Satellite: ``compressed_bytes`` must not count zero-padding rows."""

    def test_clamp_to_true_payload(self):
        jax = pytest.importorskip("jax")
        from repro.kernels.quant import compressed_bytes, quantize

        n = 1000  # not a multiple of row_len
        x = jax.numpy.asarray(np.random.RandomState(0).randn(n).astype(np.float32))
        q, s, shape = quantize(x, row_len=256, interpret=True)
        assert q.size == 1024  # padded to the row grid
        true = compressed_bytes(q, s, num_elements=n)
        padded = compressed_bytes(q, s)
        assert true == n * 1 + s.size * 4
        assert padded > true  # the old accounting over-reported
        # and the true ratio matches the codec's wire size formula minus
        # the framing header
        c = get_codec("int8")
        assert true == c.wire_nbytes(4 * n, "float32") - 20

    def test_exact_multiple_unchanged(self):
        jax = pytest.importorskip("jax")
        from repro.kernels.quant import compressed_bytes, quantize

        x = jax.numpy.asarray(np.ones((256, 4), np.float32))
        q, s, _ = quantize(x, row_len=256, interpret=True)
        assert compressed_bytes(q, s) == compressed_bytes(q, s, num_elements=1024)


class TestStoreWriteGuards:
    """Satellite: a dead worker must refuse writes like it refuses reads."""

    def _store(self):
        st = WorkerStore("w0")
        st.register({"t": np.arange(1024, dtype=np.float32)})
        return st

    def test_write_range_refuses_failed_store(self):
        st = self._store()
        st.failed = True
        with pytest.raises(TransportError):
            st.write_range("t", 0, np.zeros(16, np.uint8))

    def test_write_unit_refuses_failed_store(self):
        st = self._store()
        unit = st.units[0]
        st.failed = True
        with pytest.raises(TransportError):
            st.write_unit(unit, np.zeros(unit.nbytes, np.uint8))

    def test_live_store_accepts_writes(self):
        st = self._store()
        st.write_range("t", 0, np.zeros(16, np.uint8))
        unit = st.units[0]
        st.write_unit(unit, np.zeros(unit.nbytes, np.uint8))


def _add_stores(registry, replica, tensors, shard_idx=0):
    st = WorkerStore(f"{replica}/shard{shard_idx}")
    st.register(tensors)
    registry.add(replica, shard_idx, st)
    return st


class TestTransportCodec:
    """LocalTransport with a negotiated codec: decoded-bytes checksums,
    wire-byte accounting, chunk alignment."""

    def _pair(self, n=100000, dtype="float32"):
        reg = WorkerRegistry()
        x = (np.random.RandomState(1).randn(n) * 2).astype(_np_dtype(dtype))
        src = _add_stores(reg, "src", {"t": x})
        dst = _add_stores(reg, "dst", {"t": np.zeros_like(x)})
        return LocalTransport(reg), src, dst, x

    def test_pull_unit_int8(self):
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        manifest = src.build_manifest()
        tp.pull_unit("src", 0, unit, manifest.checksums[0], dst, codec="int8")
        c = get_codec("int8")
        expect = c.decode(c.encode(src.read_unit(unit), "float32"))
        assert np.array_equal(dst.read_unit(unit), expect)
        assert tp.bytes_moved == c.wire_nbytes(unit.nbytes, "float32")
        assert tp.bytes_moved < unit.nbytes * 0.26

    def test_pull_unit_raw_bit_identity(self):
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        manifest = src.build_manifest()
        tp.pull_unit("src", 0, unit, manifest.checksums[0], dst)
        assert np.array_equal(dst.read_unit(unit), src.read_unit(unit))
        assert tp.bytes_moved == unit.nbytes  # wire bytes == payload bytes

    def test_read_unit_range_alignment_enforced(self):
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        rb = get_codec("int8").row_bytes("float32")
        with pytest.raises(CodecError):
            tp.read_unit_range("src", 0, unit, rb // 2, rb, codec="int8")
        # a misaligned *length* is only legal as the final chunk
        with pytest.raises(CodecError):
            tp.read_unit_range("src", 0, unit, 0, rb + 4, codec="int8")

    def test_chunked_reassembly_matches_whole_pull(self):
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        c = get_codec("int8")
        whole = c.decode(c.encode(src.read_unit(unit), "float32"))
        rb = c.row_bytes("float32")
        per = 13 * rb
        out = np.empty(unit.nbytes, np.uint8)
        off = 0
        while off < unit.nbytes:
            step = min(per, unit.nbytes - off)
            out[off : off + step] = tp.read_unit_range(
                "src", 0, unit, off, step, codec="int8"
            )
            off += step
        assert np.array_equal(out, whole)

    def test_wire_frame_read_matches_encode(self):
        """decode=False returns the undecoded wire frame — exactly what
        the codec would emit for that range (the fused reshard path
        parses it client-side)."""
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        c = get_codec("int8")
        wire = tp.read_unit_range(
            "src", 0, unit, 0, unit.nbytes, codec="int8", decode=False
        )
        assert np.array_equal(wire, c.encode(src.read_unit(unit), "float32"))
        assert tp.bytes_moved == wire.nbytes

    def test_wire_frame_read_rejects_base_referencing_codec(self):
        """A delta frame is undecodable without the destination's held
        base — wire-mode reads must refuse it up front."""
        tp, src, dst, x = self._pair()
        unit = src.units[0]
        with pytest.raises(CodecError):
            tp.read_unit_range(
                "src", 0, unit, 0, unit.nbytes, codec="delta:int8",
                decode=False,
            )

    def test_compact_bucket_mixed_dtypes_passthrough(self):
        reg = WorkerRegistry()
        tensors = {
            "a": np.ones(100, np.float32),
            "b": np.arange(100, dtype=np.int32),
        }
        src = _add_stores(reg, "src", tensors)
        dst = _add_stores(
            reg, "dst", {k: np.zeros_like(v) for k, v in tensors.items()}
        )
        tp = LocalTransport(reg)
        unit = src.units[0]
        assert unit.is_compact and src.unit_dtype(unit) is None
        tp.pull_unit("src", 0, unit, src.build_manifest().checksums[0], dst, codec="int8")
        # mixed-dtype bucket rides as tagged passthrough: bit-exact
        assert np.array_equal(dst.get("a"), tensors["a"])
        assert np.array_equal(dst.get("b"), tensors["b"])

    def test_unit_dtype_resolution(self):
        metas = {}
        st = WorkerStore("w")
        st.register(
            {
                "big": np.zeros(1 << 20, np.float32),  # standalone unit
                "t1": np.zeros(128, np.float32),
                "t2": np.zeros(128, np.float32),
            }
        )
        by_unit = {u.name: st.unit_dtype(u) for u in st.units}
        assert by_unit["big"] == "float32"
        compact = [u for u in st.units if u.is_compact][0]
        assert st.unit_dtype(compact) == "float32"  # homogeneous bucket
        del metas


class TestNegotiation:
    """Server-side per-link-class codec negotiation."""

    def _open(self, s, name, dc, shards=1, model="m"):
        for i in range(shards):
            s.open(
                model,
                name,
                shards,
                i,
                worker=WorkerInfo(f"{name}/s{i}", f"{dc}/{name}", dc),
            )
            s.register(model, name, i)

    def _publish(self, s, name, version=0, units=4, shards=1, model="m"):
        from repro.transfer.simcluster import make_manifest

        for i in range(shards):
            s.publish(
                model, name, i, version, make_manifest([1 << 20] * units), op_id=version
            )

    def test_wan_slices_default_int8(self):
        s = ReferenceServer()
        self._open(s, "pub", "dc0")
        self._publish(s, "pub")
        self._open(s, "r", "dc1")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.transport == "tcp" and a.codec == "int8"
        assert all(sl.codec == "int8" for sl in a.slices(4))

    def test_intra_dc_stays_raw(self):
        s = ReferenceServer()
        self._open(s, "pub", "dc0")
        self._publish(s, "pub")
        self._open(s, "r", "dc0")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.transport == "rdma" and a.codec == "raw"
        assert all(sl.codec == "raw" for sl in a.slices(4))

    def test_resharded_unquantizable_payload_degrades_to_raw(self):
        """Resharded pulls are codec-capable, but a lossy codec needs a
        quantizable payload: uint8 source manifests force the negotiation
        down to raw (and count the degrade)."""
        from repro.transfer.simcluster import make_layout_manifests

        s = ReferenceServer()
        manifests = make_layout_manifests([1 << 20] * 4, 2, dtype="uint8")
        for i in range(2):
            s.open(
                "m", "pub", 2, i, worker=WorkerInfo(f"pub/s{i}", "dc0/pub", "dc0")
            )
            s.register("m", "pub", i)
            s.publish("m", "pub", i, 0, manifests[i], op_id=0)
        self._open(s, "r", "dc1", shards=1)
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.resharded and a.transport == "tcp"
        assert a.codec == "raw"
        assert all(sl.codec == "raw" for sl in a.sources)
        assert s.stats["codec_degrades"] >= 1

    def test_reroute_preserves_wan_codec(self):
        s = ReferenceServer()
        self._open(s, "pub0", "dc0")
        self._publish(s, "pub0")
        self._open(s, "pub1", "dc0")
        # pub1 holds the version too (replicate + complete)
        a1 = s.begin_replicate("m", "pub1", 0, 0, op_id=0)
        s.update_progress("m", "pub1", 0, 0, 4)
        s.complete_replicate("m", "pub1", 0, 0, op_id=1)
        self._open(s, "r", "dc1")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.codec == "int8"
        s.report_transfer_failure("m", "r", a.source)
        a2 = s.get_assignment("m", "r")
        assert a2 is not None and a2.source != a.source
        assert a2.codec == "int8"  # still WAN-crossing after the re-plan

    def test_custom_and_invalid_wan_codec(self):
        s = ReferenceServer(wan_codec="fixed:0.25")
        assert s.config()["wan_codec"] == "fixed:0.25"
        with pytest.raises(TensorHubError):
            ReferenceServer(wan_codec="zstd")

    def test_failover_preserves_wan_codec(self):
        from repro.core.failover import recover

        log = OpLog()
        s = ReferenceServer(wan_codec="raw", log=log)
        self._open(s, "pub", "dc0")
        self._publish(s, "pub")
        s.crash()
        recovered = recover(log)
        assert recovered.config()["wan_codec"] == "raw"
        self._open(recovered, "r", "dc1")
        a = recovered.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.codec == "raw"

    def _seed_correlated(self, s):
        """pub (dc0) retires v0 and publishes v1 after r (dc1) fully
        replicated v0 — the correlated-update shape delta targets."""
        self._open(s, "pub", "dc0")
        self._publish(s, "pub", version=0)
        self._open(s, "r", "dc1")
        s.begin_replicate("m", "r", 0, 0, op_id=0)
        s.update_progress("m", "r", 0, 0, 4)
        s.complete_replicate("m", "r", 0, 0, op_id=1)
        s.unpublish("m", "pub", 0, op_id=10)
        self._publish(s, "pub", version=1)

    def test_update_negotiates_delta(self):
        s = ReferenceServer()
        self._seed_correlated(s)
        d = s.begin_update("m", "r", 0, "latest", op_id=2)
        assert d.updated and d.assignment.codec == "delta:int8"
        assert all(sl.codec == "delta:int8" for sl in d.assignment.slices(4))
        assert s.stats["delta_assignments"] == 1

    def test_fresh_dest_negotiates_plain(self):
        s = ReferenceServer()
        self._seed_correlated(s)
        self._open(s, "fresh", "dc1")
        a = s.begin_replicate("m", "fresh", 0, "latest", op_id=0)
        assert a.codec == "int8"  # no prior version to diff against

    def test_wan_delta_disabled(self):
        s = ReferenceServer(wan_delta=False)
        assert s.config()["wan_delta"] is False
        self._seed_correlated(s)
        d = s.begin_update("m", "r", 0, "latest", op_id=2)
        assert d.updated and d.assignment.codec == "int8"
        assert s.stats["delta_assignments"] == 0

    def test_prior_version_mismatch_negotiates_plain(self):
        """Source retired v1 while dest still holds v0: residuals against
        the wrong base are never negotiated."""
        s = ReferenceServer()
        self._seed_correlated(s)
        s.unpublish("m", "pub", 0, op_id=20)
        self._publish(s, "pub", version=2)
        d = s.begin_update("m", "r", 0, "latest", op_id=2)
        assert d.updated and d.assignment.version == 2
        assert d.assignment.codec == "int8"

    def test_non_delta_capable_wan_codec_skips_delta(self):
        s = ReferenceServer(wan_codec="fixed:0.5")
        self._seed_correlated(s)
        d = s.begin_update("m", "r", 0, "latest", op_id=2)
        assert d.updated and d.assignment.codec == "fixed:0.5"

    def test_aliased_unquantizable_payload_degrades_to_raw(self):
        """An aliased layout (same shard count, different unit
        boundaries) runs the interval-read path, which is codec-capable —
        but this source publishes uint8 units, so the lossy codec can't
        align to a quantization row grid and the pull degrades to raw at
        plan time, counting the degrade."""
        from repro.transfer.simcluster import make_manifest

        s = ReferenceServer()
        self._open(s, "pub", "dc0")
        self._publish(s, "pub", version=0)
        self._open(s, "alias", "dc0")
        # same shard count, same bytes, different unit boundaries
        s.publish("m", "alias", 0, 0, make_manifest([2 << 20] * 2), op_id=0)
        s.fail_replica("m", "pub")
        self._open(s, "r", "dc1")
        a = s.begin_replicate("m", "r", 0, 0, op_id=0)
        assert a.source == "alias" and a.codec == "raw"
        assert s.stats["codec_degrades"] >= 1

    def test_failover_preserves_wan_delta(self):
        """The delta negotiation settings and the prior-version bookkeeping
        they key on must replay bit-identically across a controller crash
        — including a live delta assignment."""
        from repro.core.failover import recover, state_digest

        log = OpLog()
        s = ReferenceServer(wan_delta=False, log=log)
        self._seed_correlated(s)
        s.begin_update("m", "r", 0, "latest", op_id=2)
        digest = state_digest(s)
        s.crash()
        recovered = recover(log)
        assert recovered.config()["wan_delta"] is False
        assert state_digest(recovered) == digest
        # and the delta path itself survives replay: a wan_delta server
        # that negotiated delta:int8 pre-crash re-derives it post-crash
        log2 = OpLog()
        s2 = ReferenceServer(log=log2)
        self._seed_correlated(s2)
        d = s2.begin_update("m", "r", 0, "latest", op_id=2)
        assert d.assignment.codec == "delta:int8"
        digest2 = state_digest(s2)
        s2.crash()
        rec2 = recover(log2)
        assert rec2.config()["wan_delta"] is True
        assert state_digest(rec2) == digest2


def _threaded_tensors(seed=2.0):
    """Model-zoo-ish shard: a standalone f32 unit, a standalone bf16 unit
    with a non-multiple-of-256 element count, and tiny tensors that
    compact into a (homogeneous) bucket."""
    import ml_dtypes

    rng = np.random.RandomState(int(seed))
    return {
        "w_f32": (rng.randn(1 << 20) * seed).astype(np.float32),  # 4 MiB
        "w_bf16": (rng.randn((1 << 20) + 777) * seed).astype(ml_dtypes.bfloat16),
        "tiny0": (rng.randn(2048) * seed).astype(np.float32),
        "tiny1": (rng.randn(2048) * seed).astype(np.float32),
    }


def _correlated_tensors(nrows=4096, changed_rows=1024, mutate=False):
    """Two correlated weight versions (one RL step apart): v1 and a v2
    that differs in exactly ``changed_rows`` of the ``nrows`` quant rows."""
    rng = np.random.default_rng(21)
    w = rng.standard_normal((nrows, 256)).astype(np.float32)
    if mutate:
        w[:changed_rows] = w[:changed_rows] * 1.001 + 0.01
    return {"w": w}


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
        t.join(timeout=120)
    if errs:
        raise errs[0]


class TestThreadedCrossDC:
    """End-to-end through the threaded client: real bytes, negotiated
    codecs, checksums verified over decoded bytes."""

    def _publish(self, hub, dc="dc0"):
        pub = hub.open("m", "pub", 1, 0, datacenter=dc)
        pub.register(_threaded_tensors())
        pub.publish(0)
        return pub

    def _reader(self, hub, name, dc="dc1", **kw):
        h = hub.open("m", name, 1, 0, datacenter=dc, **kw)
        h.register({k: np.zeros_like(v) for k, v in _threaded_tensors().items()})
        return h

    def _max_rel(self, reader, src_tensors):
        worst = 0.0
        for k, v in src_tensors.items():
            got = np.asarray(reader.store.get(k), np.float32)
            want = np.asarray(v, np.float32)
            denom = max(float(np.max(np.abs(want))), 1e-12)
            worst = max(worst, float(np.max(np.abs(got - want))) / denom)
        return worst

    def test_int8_wan_pull(self):
        hub = TensorHubClient(ReferenceServer())
        self._publish(hub)
        total = sum(v.nbytes for v in _threaded_tensors().values())
        r = self._reader(hub, "r")
        r.replicate("latest")
        assert self._max_rel(r, _threaded_tensors()) < 0.01
        # wire bytes: f32 unit at ~0.254x, bf16 at ~0.508x, bucket ~0.254x
        assert hub.transport.bytes_moved < 0.45 * total
        r.close()

    def test_raw_reproduces_byte_counts_bit_for_bit(self):
        hub = TensorHubClient(ReferenceServer(wan_codec="raw"))
        self._publish(hub)
        src = _threaded_tensors()
        total = sum(v.nbytes for v in src.values())
        r = self._reader(hub, "r")
        r.replicate("latest")
        assert hub.transport.bytes_moved == total  # exactly today's wire
        for k, v in src.items():
            assert np.array_equal(
                r.store.get(k).view(np.uint8), v.view(np.uint8)
            )
        r.close()

    def test_chain_off_lossy_replica_verifies(self):
        """A dc1 reader seeded over int8 re-registers its own checksums;
        a second dc1 reader then raw-chains off it with end-to-end
        verification against the *decoded* bytes."""
        server = ReferenceServer()
        hub = TensorHubClient(server)
        self._publish(hub)
        r1 = self._reader(hub, "r1")
        r1.replicate("latest")
        moved = hub.transport.bytes_moved
        r2 = self._reader(hub, "r2")
        r2.replicate("latest")
        # r2 pulled intra-DC (raw): full payload bytes, from r1's copy
        total = sum(v.nbytes for v in _threaded_tensors().values())
        assert hub.transport.bytes_moved - moved == total
        for k in _threaded_tensors():
            assert np.array_equal(
                r2.store.get(k).view(np.uint8), r1.store.get(k).view(np.uint8)
            )
        # and the manifest r2 verified against carries real checksums now
        m = server.replica_manifest("m", 0, "r1", 0)
        assert any(m.checksums)
        r2.close()
        r1.close()

    def test_divergence_propagates_down_raw_chains(self):
        """Regression: r2 raw-chains off the int8-seeded r1, so r2's
        bytes diverge from the publisher's even though r2's own plan was
        lossless. A third reader sourcing from r2 (after r1 is evicted)
        must verify against r2's re-registered checksums, not the
        publisher family's — without divergence propagation this raised
        ChecksumError."""
        server = ReferenceServer()
        hub = TensorHubClient(server)
        self._publish(hub)
        r1 = self._reader(hub, "r1")
        r1.replicate("latest")
        r2 = self._reader(hub, "r2")
        r2.replicate("latest")
        hub.registry.fail_replica("r1")
        server.fail_replica("m", "r1")
        r3 = self._reader(hub, "r3")
        r3.replicate("latest", timeout=60)
        for k in _threaded_tensors():
            assert np.array_equal(
                r3.store.get(k).view(np.uint8), r2.store.get(k).view(np.uint8)
            )
        # r2 registered its own (divergent) manifest with real checksums
        m = server.replica_manifest("m", 0, "r2", 0)
        assert any(m.checksums)

    def test_chunked_giant_unit_matches_unchunked(self):
        srv = ReferenceServer()
        hub_whole = TensorHubClient(srv)
        self._publish(hub_whole)
        r_whole = self._reader(hub_whole, "rw")
        r_whole.replicate("latest")
        # fresh server/hub with chunking: 4 MiB unit -> 1 MiB chunks
        srv2 = ReferenceServer()
        hub_chunk = TensorHubClient(srv2, chunk_bytes=1 << 20)
        pub2 = hub_chunk.open("m", "pub", 1, 0, datacenter="dc0")
        pub2.register(_threaded_tensors())
        pub2.publish(0)
        r_chunk = self._reader(hub_chunk, "rc")
        r_chunk.replicate("latest")
        for k in _threaded_tensors():
            assert np.array_equal(
                r_chunk.store.get(k).view(np.uint8),
                r_whole.store.get(k).view(np.uint8),
            ), f"chunked reassembly diverged for {k}"

    def test_nan_weights_cross_dc(self):
        """End-to-end: a published shard containing NaN still replicates
        over the default int8 WAN negotiation (bit-exact passthrough for
        the poisoned unit, quantized for the rest)."""
        hub = TensorHubClient(ReferenceServer())
        tensors = _threaded_tensors()
        tensors["w_f32"][1234] = np.nan
        pub = hub.open("m", "pub", 1, 0, datacenter="dc0")
        pub.register(tensors)
        pub.publish(0)
        r = hub.open("m", "r", 1, 0, datacenter="dc1")
        r.register({k: np.zeros_like(v) for k, v in tensors.items()})
        r.replicate(0, timeout=60)
        # the poisoned tensor arrived bit-exact (passthrough)
        assert np.array_equal(
            r.store.get("w_f32").view(np.uint8), tensors["w_f32"].view(np.uint8)
        )

    def test_sibling_with_divergent_checksums_dropped(self):
        """_validated_slices drops a same-layout sibling whose manifest
        checksums differ from the primary's — its bytes diverged (e.g. an
        int8-descended replica pooled with a faithful one), so verifying
        its units against the primary's checksums would spuriously fail."""
        from repro.core.meta import SourceSlice

        hub = TensorHubClient(ReferenceServer())
        rng = np.random.RandomState(0)
        a = hub.open("m", "a", 1, 0, datacenter="dc0")
        a.register({"t": rng.randn(1 << 20).astype(np.float32)})
        a.publish(0)
        b = hub.open("m", "b", 1, 0, datacenter="dc0")
        b.register({"t": rng.randn(1 << 20).astype(np.float32)})  # different bytes
        # forge b as a second holder of v0 with its own (divergent) manifest
        hub.server.publish("m", "b", 0, 0, b.store.build_manifest(), op_id=0)
        reader = hub.open("m", "r", 1, 0, datacenter="dc0")
        reader.register({"t": np.zeros(1 << 20, np.float32)})
        manifest_a = hub.server.replica_manifest("m", 0, "a", 0)

        def sl(name):
            return SourceSlice(
                source=name, source_kind="gpu", transport="rdma",
                start_unit=0, stop_unit=1,
            )

        kept = reader._validated_slices([sl("a"), sl("b")], 0, manifest_a)
        assert [s.source for s in kept] == ["a"]

    def test_dest_preemption_not_blamed_on_source(self):
        """Regression: the new write guard makes a preempted DESTINATION
        raise TransportError; the client must surface it rather than
        report the healthy source dead (which would evict it
        cluster-wide)."""
        server = ReferenceServer()
        hub = TensorHubClient(server)
        self._publish(hub)
        r = self._reader(hub, "r")
        r.store.failed = True  # dest preempted before/while pulling
        with pytest.raises(TransportError):
            r.replicate("latest", timeout=30)
        info = server._models["m"].replicas.get("pub")
        assert info is not None and not info.failed  # source still healthy

    def test_update_path_uses_wan_codec(self):
        hub = TensorHubClient(ReferenceServer())
        pub = self._publish(hub)
        r = self._reader(hub, "r")
        r.replicate(0)
        pub.unpublish()
        pub.store.register(_threaded_tensors(seed=5.0))
        pub.publish(1)
        before = hub.transport.bytes_moved
        assert r.update("latest")
        total = sum(v.nbytes for v in _threaded_tensors().values())
        assert hub.transport.bytes_moved - before < 0.45 * total
        assert self._max_rel(r, _threaded_tensors(seed=5.0)) < 0.01

    def _correlated_update(
        self, *, wan_delta=True, scramble_dest=False, drop_source_base=False
    ):
        """publish v0 -> r replicates cross-DC -> publish a correlated v1
        -> r updates. Returns (update-leg wire bytes, r's final tensor,
        hub, server)."""
        server = ReferenceServer(wan_delta=wan_delta)
        hub = TensorHubClient(server)
        pub = hub.open("m", "pub", 1, 0, datacenter="dc0")
        pub.register(_correlated_tensors())
        pub.publish(0)
        r = hub.open("m", "r", 1, 0, datacenter="dc1")
        r.register({"w": np.zeros((4096, 256), np.float32)})
        r.replicate(0)
        pub.unpublish()
        if drop_source_base:
            pub.store.drop_base()
        pub.store.register(_correlated_tensors(mutate=True))
        pub.publish(1)
        if scramble_dest:
            r.store.get("w")[:] = 0.0  # base evicted/diverged mid-plan
        before = hub.transport.bytes_moved
        assert r.update("latest")
        wire = hub.transport.bytes_moved - before
        return wire, r.store.get("w").copy(), hub, server

    def test_delta_update_ships_fewer_wan_bytes(self):
        wire_i8, out_i8, _, _ = self._correlated_update(wan_delta=False)
        wire_d, out_d, hub, server = self._correlated_update()
        assert server.stats["delta_assignments"] >= 1
        assert hub.transport.delta_stale_fallbacks == 0
        # 25% changed rows: ~4x fewer WAN bytes than plain int8
        assert wire_d < 0.3 * wire_i8
        want = _correlated_tensors(mutate=True)["w"]
        assert float(np.max(np.abs(out_d - want))) / float(np.max(np.abs(want))) < 0.01
        # unchanged rows arrive bit-identical to the plain-int8 outcome
        assert np.array_equal(out_d[1024:], out_i8[1024:])

    def test_delta_stale_base_falls_back_byte_identical(self):
        """A destination whose held base was evicted mid-plan decodes the
        frame's digest mismatch as StaleBaseError, transparently re-pulls
        plain int8, and lands byte-identical to a non-delta update."""
        wire_i8, out_i8, _, _ = self._correlated_update(wan_delta=False)
        wire_s, out_s, hub, _ = self._correlated_update(scramble_dest=True)
        assert hub.transport.delta_stale_fallbacks >= 1
        assert np.array_equal(out_s, out_i8)
        # both the refused delta frame and the int8 re-send crossed the wire
        assert wire_s > wire_i8

    def test_delta_source_without_base_sends_plain_int8(self):
        """A source that dropped its base snapshot (steal/failover onto a
        replica that can't serve residuals) emits plain int8 fallback
        frames at encode time — no stale event, byte-identical result."""
        wire_i8, out_i8, _, _ = self._correlated_update(wan_delta=False)
        wire_f, out_f, hub, _ = self._correlated_update(drop_source_base=True)
        assert hub.transport.delta_stale_fallbacks == 0
        assert wire_f == wire_i8
        assert np.array_equal(out_f, out_i8)

    @pytest.mark.timeout(120)
    def test_truncated_frame_heals_via_corrupt_quarantine(self):
        """Regression: a CodecError raised during wire decode used to
        crash the puller. A fault-injected truncated int8 frame must now
        route through the healing path — corrupt evidence, quarantine,
        alternate-source re-fetch — and finish with good bytes."""
        from repro.core.client import RetryPolicy
        from repro.transfer.faults import (
            FaultPlan,
            FaultSpec,
            ThreadedFaultInjector,
        )

        server = ReferenceServer(quarantine_threshold=2, quarantine_probation=60.0)
        inj = ThreadedFaultInjector(
            FaultPlan(seed=11, faults=(FaultSpec("truncate", "pub", severity=1.0),))
        )
        clean = TensorHubClient(server)
        hub = TensorHubClient(
            server,
            registry=clean.registry,
            retry_policy=RetryPolicy(
                fail_detect=0.3, retry_limit=5, retry_backoff=0.01,
                hedge_threshold=8.0, hedge_min_samples=16,
            ),
            faults=inj,
        )
        rng = np.random.RandomState(30)
        want = (rng.randn(1 << 18) * 3).astype(np.float32)
        pub = clean.open("m", "pub", 1, 0, datacenter="dc0")
        pub.register({"w": want.copy()})
        pub.publish(0)
        # healthy alternate source ("pub" sorts first, so the faulty
        # replica is the deterministic initial pick)
        spare = clean.open("m", "spare", 1, 0, datacenter="dc0")
        spare.register({"w": np.zeros_like(want)})
        spare.replicate("latest")
        dest = hub.open("m", "dest", 1, 0, datacenter="dc1")
        dest.register({"w": np.zeros_like(want)})
        inj.arm()
        dest.replicate("latest", timeout=60)
        got = dest.store.get("w")
        rel = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
        assert rel < 0.01  # int8-decoded bytes from the healthy source
        assert server.stats["corrupt_reports"] >= 1
        assert server.stats["quarantines"] >= 1
        assert server.stats["evictions"] == 0


class TestSimCodec:
    """Fluid plane: wire bytes derive from the codec's per-manifest ratio."""

    def _wan_bytes(self, **kw):
        from repro.transfer.simcluster import SimCluster

        cl = SimCluster(**kw)
        units = [int(1e9)] * 4
        tr = cl.add_replica("m", "tr", 2, datacenter="dc0", unit_bytes=units)
        ro = cl.add_replica("m", "ro", 2, datacenter="dc1", unit_bytes=units)
        tr.open()
        ro.open()
        cl.run()
        tr.publish(0)
        cl.run()
        ro.replicate("latest")
        cl.run()
        return sum(b for n, b in cl.net.link_bytes.items() if ":vpc_up" in n)

    def test_int8_default_vs_raw(self):
        raw = self._wan_bytes(wan_codec="raw")
        q = self._wan_bytes()  # default int8
        assert math.isclose(raw, 8e9, rel_tol=1e-6)
        ratio = wire_ratio(get_codec("int8"), [int(1e9)] * 4, "float32")
        assert math.isclose(q, raw * ratio, rel_tol=1e-6)
        assert 3.8 < raw / q < 4.0  # the ~3.9x WAN reduction

    def test_intra_dc_unaffected_by_wan_codec(self):
        from repro.transfer.simcluster import SimCluster

        for codec in ("raw", "int8"):
            cl = SimCluster(wan_codec=codec)
            units = [int(1e9)] * 4
            a = cl.add_replica("m", "a", 1, datacenter="dc0", unit_bytes=units)
            b = cl.add_replica("m", "b", 1, datacenter="dc0", unit_bytes=units)
            a.open()
            b.open()
            cl.run()
            a.publish(0)
            cl.run()
            b.replicate("latest")
            cl.run()
            rdma = sum(b_ for n, b_ in cl.net.link_bytes.items() if ":up" in n)
            assert math.isclose(rdma, 4e9, rel_tol=1e-6)

    def _reshard_wan_bytes(self, **kw):
        from repro.transfer.simcluster import SimCluster

        cl = SimCluster(**kw)
        g = [int(1e9)] * 4
        tr = cl.add_replica("m", "tr", 2, datacenter="dc0", global_unit_bytes=g)
        ro = cl.add_replica("m", "ro", 4, datacenter="dc1", global_unit_bytes=g)
        tr.open()
        ro.open()
        cl.run()
        tr.publish(0)
        cl.run()
        ev = ro.replicate("latest")
        cl.run()
        assert ev.triggered and ev.error is None
        return sum(b for n, b in cl.net.link_bytes.items() if ":vpc_up" in n)

    def test_cross_dc_reshard_forced_raw_bit_exact(self):
        """wan_codec="raw": resharded interval flows move exactly the
        payload bytes (zero row-grid widening on a raw plan)."""
        wan = self._reshard_wan_bytes(wan_codec="raw")
        assert math.isclose(wan, 4e9, rel_tol=1e-6)

    def test_cross_dc_reshard_negotiates_int8(self):
        """The default WAN codec now rides the resharded interval path:
        wire bytes shrink by the codec's ratio (>= 3.5x vs forced raw)."""
        raw = self._reshard_wan_bytes(wan_codec="raw")
        coded = self._reshard_wan_bytes()
        assert raw / coded >= 3.5

    def test_legacy_tcp_compression_scales_resharded_flows(self):
        """Regression: the deprecated scalar scaled EVERY WAN TCP flow —
        resharded interval flows included (codec negotiation keeps those
        raw, so the alias must bypass it to preserve old accounting)."""
        import warnings as _warnings

        from repro.transfer.simcluster import SimCluster

        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", DeprecationWarning)
            cl = SimCluster(tcp_compression=0.5)
        g = [int(1e9)] * 4
        tr = cl.add_replica("m", "tr", 2, datacenter="dc0", global_unit_bytes=g)
        ro = cl.add_replica("m", "ro", 4, datacenter="dc1", global_unit_bytes=g)
        tr.open()
        ro.open()
        cl.run()
        tr.publish(0)
        cl.run()
        ev = ro.replicate("latest")
        cl.run()
        assert ev.triggered and ev.error is None
        wan = sum(b for n, b in cl.net.link_bytes.items() if ":vpc_up" in n)
        assert math.isclose(wan, 4e9 * 0.5, rel_tol=1e-6)

    def test_delta_reshard_resolves_to_base(self):
        """A resharded assignment carrying a delta codec collapses to the
        delta's base on the interval path (no held prior version exists
        at interval granularity): one policy point, both data planes."""
        from repro.transfer.codec import reshard_wire_codec

        assert reshard_wire_codec("delta:int8") == "int8"
        assert reshard_wire_codec("delta:raw") == "raw"
        assert reshard_wire_codec("int8") == "int8"
        assert reshard_wire_codec("raw") == "raw"

    def _update_wan_bytes(self, **kw):
        """Warm update flow: publish v0, replicate, retire, publish v1,
        update — the correlated shape where delta is negotiated. Returns
        the update leg's WAN bytes."""
        from repro.transfer.simcluster import SimCluster

        cl = SimCluster(**kw)
        units = [4 << 20]
        tr = cl.add_replica("m", "tr", 1, datacenter="dc0", unit_bytes=units)
        ro = cl.add_replica("m", "ro", 1, datacenter="dc1", unit_bytes=units)
        tr.open()
        ro.open()
        cl.run()
        tr.publish(0)
        cl.run()
        ro.replicate("latest")
        cl.run()
        before = dict(cl.net.link_bytes)
        tr.unpublish()
        cl.run()
        tr.publish(1)
        cl.run()
        ev = ro.update("latest")
        cl.run()
        assert ev.triggered and ev.error is None
        wan = sum(
            b - before.get(n, 0)
            for n, b in cl.net.link_bytes.items()
            if ":vpc_up" in n
        )
        return wan, cl

    def test_delta_update_models_kept_fraction(self):
        wan_i8, _ = self._update_wan_bytes(wan_codec="int8", wan_delta=False)
        wan_d, cl = self._update_wan_bytes(
            wan_codec="int8", wan_delta=True, delta_kept_frac=0.25
        )
        assert cl.server.stats["delta_assignments"] >= 1
        # byte model follows the codec's own sizing exactly
        expect = get_codec("delta:int8").wire_nbytes_at(4 << 20, "float32", 0.25)
        assert math.isclose(wan_d, expect, rel_tol=1e-6)
        assert wan_d < 0.3 * wan_i8

    def test_threaded_and_sim_delta_parity(self):
        """WAN bytes for the same correlated update (25% of rows changed,
        one 4 MiB f32 unit) agree across the two data planes."""
        wan_sim, _ = self._update_wan_bytes(
            wan_codec="int8", wan_delta=True, delta_kept_frac=0.25
        )
        s = ReferenceServer(wan_codec="int8")
        hub = TensorHubClient(s)
        pub = hub.open("m", "pub", 1, 0, datacenter="dc0")
        pub.register(_correlated_tensors())
        pub.publish(0)
        r = hub.open("m", "r", 1, 0, datacenter="dc1")
        r.register({"w": np.zeros((4096, 256), np.float32)})
        r.replicate(0)
        pub.unpublish()
        pub.store.register(_correlated_tensors(mutate=True))
        pub.publish(1)
        before = hub.transport.bytes_moved
        assert r.update("latest")
        wan_thr = hub.transport.bytes_moved - before
        assert abs(wan_thr - wan_sim) / wan_sim < 0.02
