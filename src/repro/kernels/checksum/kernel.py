"""End-to-end transfer checksum as a Pallas TPU kernel (paper 4.6).

The paper computes per-tensor checksums on the GPU, overlapped with the
RDMA transfer. TPU adaptation: a grid-sequential reduction over the
tensor in its own dtype and shape, so a tensor in HBM is read once and
never copied into a word array. Element ``j`` of an ``n``-bit dtype is
bits ``n * (j % k)`` and up of the little-endian 32-bit word ``j // k``
(``k = 32 / n``), so each element's share of ``(s1, s2)`` is computed
where it stands: ``s1 += e << shift`` and ``s2 += weight(j // k) *
(e << shift)``. Each step folds its block into per-column partial sums
held in the output block, which maps to the same tile across the row
steps of one column block (TPU grids execute sequentially, so
read-modify-write accumulation across them is well-defined); the
partials are summed outside the kernel.

Mosaic implements no reductions over unsigned integers, so the kernel
works in int32: two's-complement sums and products wrap exactly like
uint32, and the result, bitcast back, is bit-identical to the host NumPy
implementation in ``repro.transfer.checksum``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: int32 bytes per grid step: bounds the kernel's VMEM intermediates
#: (a handful of block-sized int32 arrays) well under the scoped limit
BLOCK_BYTES = 1 << 20
_LANES = 128
_SUBLANES = 8
_INT = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}


def _checksum_kernel(x_ref, out_ref, *, bits, cols, block_rows, block_cols):
    cb, rb = pl.program_id(0), pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = jax.lax.bitcast_convert_type(x_ref[...], _INT[bits]).astype(jnp.int32)
    if bits < 32:
        v = v & ((1 << bits) - 1)
    shape = v.shape
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + rb * block_rows
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + cb * block_cols
    # element index; it may wrap, but only its low 18 bits are read
    j = row * cols + col
    k = 32 // bits
    if k > 1:
        v = v << ((j & (k - 1)) * bits)
    weight = ((j & (65536 * k - 1)) // k) + 1
    out_ref[0] += jnp.sum(v, axis=0, keepdims=True)
    out_ref[1] += jnp.sum(v * weight, axis=0, keepdims=True)


def _largest_block(n: int, unit: int, limit: int) -> int:
    """Largest multiple of ``unit`` that divides ``n`` and is at most
    ``limit`` (``n`` itself when ``n`` is at most ``unit``)."""
    if n <= unit:
        return n
    best = unit
    for b in range(unit, min(n, limit) + 1, unit):
        if n % b == 0:
            best = b
    return best


def checksum_tensor(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Any 1-, 2- or 4-byte dtype, any shape -> uint32[2] (s1, s2) over
    the little-endian bytes of ``x`` in C order."""
    bits = 8 * x.dtype.itemsize
    if bits not in _INT:
        raise ValueError(f"checksum of {x.dtype}: 1-, 2- or 4-byte dtypes only")
    if x.ndim >= 2 and x.shape[-1] % _LANES == 0:
        x2 = x.reshape(-1, x.shape[-1])  # merges leading dims, no copy
    else:
        flat = x.reshape(-1)
        # trailing zero elements add nothing to either sum
        flat = jnp.pad(flat, (0, (-flat.shape[0]) % _LANES))
        x2 = flat.reshape(-1, _LANES)
    rows, cols = x2.shape
    if rows > _SUBLANES and rows % _SUBLANES:
        x2 = jnp.pad(x2, ((0, (-rows) % _SUBLANES), (0, 0)))
        rows = x2.shape[0]
    block_cols = _largest_block(cols, _LANES, BLOCK_BYTES // 4 // _SUBLANES)
    block_rows = _largest_block(
        rows, _SUBLANES, max(_SUBLANES, BLOCK_BYTES // 4 // block_cols)
    )

    partial = pl.pallas_call(
        functools.partial(
            _checksum_kernel,
            bits=bits,
            cols=cols,
            block_rows=block_rows,
            block_cols=block_cols,
        ),
        grid=(cols // block_cols, rows // block_rows),
        in_specs=[pl.BlockSpec((block_rows, block_cols), lambda c, r: (r, c))],
        out_specs=pl.BlockSpec((2, 1, block_cols), lambda c, r: (0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((2, 1, cols), jnp.int32),
        interpret=interpret,
    )(x2)
    partial = jax.lax.bitcast_convert_type(partial, jnp.uint32)
    return jnp.sum(partial.reshape(2, -1), axis=1, dtype=jnp.uint32)


def checksum_words(words: jax.Array, *, interpret: bool = False) -> jax.Array:
    """words: uint32[N] -> uint32[2] (s1, s2)."""
    return checksum_tensor(words, interpret=interpret)
