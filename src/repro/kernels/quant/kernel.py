"""Int8 symmetric quantization as a Pallas TPU kernel.

Beyond-paper optimization (EXPERIMENTS.md Perf): cross-datacenter seeding
sends 4x fewer bytes by transferring int8 + per-row scales instead of
bf16/f32 weights, and the same kernel compresses gradients for slow-link
data parallelism. Row blocks of 256 x C tile VMEM; absmax reduction and
rounding run on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant.ref import round_quotient

_BLOCK_ROWS = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # [bR, C]
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # [bR, 1]
    scale = jnp.maximum(absmax * (1 / 127), 1e-12)  # as quantize_ref
    q = jnp.clip(round_quotient(x, scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape).astype(jnp.float32)


def quantize_rows(
    x: jax.Array, *, block_rows: int = _BLOCK_ROWS, interpret: bool = False
):
    """x: [R, C] -> (q int8 [R, C], scales f32 [R])."""
    r, c = x.shape
    block_rows = min(block_rows, r)
    pad = (-r) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = r + pad
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, c), jnp.int8),
            jax.ShapeDtypeStruct((rp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q[:r], s[:r, 0]
