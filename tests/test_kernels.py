"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
against the pure-jnp oracles, per the deliverable-(c) requirement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.checksum import checksum_ref, fold64, tensor_checksum
from repro.kernels.checksum.kernel import checksum_words
from repro.kernels.checksum.ops import host_equivalent
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.quant import dequantize, quantize, quantize_ref
from repro.kernels.quant.kernel import quantize_rows


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,hq,hkv,sq,sk,d,causal,cap",
        [
            (2, 4, 2, 128, 128, 64, True, 0.0),
            (1, 8, 8, 256, 256, 128, True, 50.0),  # gemma2-style softcap
            (2, 4, 1, 96, 160, 64, False, 0.0),  # ragged, cross-len, MQA
            (1, 2, 2, 384, 384, 256, True, 0.0),  # gemma2 head_dim 256
            (1, 16, 4, 64, 64, 128, True, 0.0),  # GQA 4:1
        ],
    )
    def test_against_oracle(self, b, hq, hkv, sq, sk, d, causal, cap):
        ks = jax.random.split(jax.random.PRNGKey(sq + d), 3)
        q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, sk, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hkv, sk, d), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, softcap=cap, interpret=True)
        ref = attention_ref(q, k, v, causal=causal, softcap=cap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(dtype)
        k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
        v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_ref(q, k, v)
        assert out.dtype == dtype
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
        )

    def test_block_shape_sweep(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 2, 200, 64))
        k = jax.random.normal(ks[1], (1, 2, 200, 64))
        v = jax.random.normal(ks[2], (1, 2, 200, 64))
        ref = attention_ref(q, k, v)
        for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
            out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestChecksum:
    @pytest.mark.parametrize(
        "shape,dtype",
        [
            ((1024,), jnp.float32),
            ((333, 7), jnp.bfloat16),
            ((65536,), jnp.float32),
            ((1,), jnp.float32),
            ((100001,), jnp.int32),
            ((20, 3, 256), jnp.bfloat16),  # in place, 2-D grid
            ((9, 1152), jnp.float32),  # rows padded to a sublane multiple
            ((13, 384), jnp.int8),  # four elements per word
        ],
    )
    def test_kernel_matches_host(self, shape, dtype):
        if dtype in (jnp.int32, jnp.int8):
            x = jnp.arange(np.prod(shape), dtype=jnp.int32).astype(dtype).reshape(shape)
        else:
            x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
        got = fold64(np.asarray(tensor_checksum(x, interpret=True)))
        assert got == host_equivalent(x)

    @settings(max_examples=30, deadline=None)
    @given(st.binary(min_size=0, max_size=4096))
    def test_kernel_matches_host_bytes(self, raw):
        from repro.transfer.checksum import checksum as host_checksum

        pad = (-len(raw)) % 4
        buf = raw + b"\0" * pad
        words = jnp.asarray(np.frombuffer(buf, np.uint32)) if buf else jnp.zeros((0,), jnp.uint32)
        if words.size == 0:
            return
        pair = checksum_words(words, interpret=True)
        assert fold64(np.asarray(pair)) == host_checksum(buf)

    def test_ref_matches_kernel(self):
        words = jax.random.bits(jax.random.PRNGKey(0), (5000,), jnp.uint32)
        np.testing.assert_array_equal(
            np.asarray(checksum_words(words, interpret=True)),
            np.asarray(checksum_ref(words)),
        )

    def test_detects_corruption_and_reorder(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (4096,))
        base = fold64(np.asarray(tensor_checksum(x, interpret=True)))
        flipped = x.at[17].set(x[17] + 1.0)
        assert fold64(np.asarray(tensor_checksum(flipped, interpret=True))) != base
        swapped = x.at[jnp.asarray([3, 400])].set(x[jnp.asarray([400, 3])])
        assert fold64(np.asarray(tensor_checksum(swapped, interpret=True))) != base


class TestQuant:
    @pytest.mark.parametrize("shape", [(64, 128), (1000, 555), (3, 7, 64)])
    def test_roundtrip(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(2), shape) * 3.0
        q, s, orig = quantize(x, row_len=128, interpret=True)
        xr = dequantize(q, s, orig)
        assert xr.shape == x.shape
        rel = float(jnp.max(jnp.abs(xr - x)) / jnp.max(jnp.abs(x)))
        assert rel < 0.01

    def test_kernel_matches_ref(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (512, 256))
        qk, sk_ = quantize_rows(x, interpret=True)
        qr, sr = quantize_ref(x)
        np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(sk_), np.asarray(sr), rtol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = (jax.random.normal(jax.random.PRNGKey(4), (256, 128)) * 2).astype(dtype)
        q, s, orig = quantize(x, row_len=128, interpret=True)
        xr = dequantize(q, s, orig, dtype=jnp.float32)
        rel = float(jnp.max(jnp.abs(xr - x.astype(jnp.float32))))
        assert rel < 0.1

    def test_compression_ratio(self):
        from repro.kernels.quant import compressed_bytes

        x = jax.random.normal(jax.random.PRNGKey(5), (1024, 1024))
        q, s, _ = quantize(x, row_len=1024, interpret=True)
        assert compressed_bytes(q, s) < x.size * 4 / 3.5  # ~4x smaller
