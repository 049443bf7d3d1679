"""Weights made from ``--seed``, and a digest of landed bytes.

Version 0 of every element is a hash of (seed, tensor, global element
index): random sign and mantissa, exponent between 2^-15 and 2^-8, so every
value is a finite normal bf16. Version k is version 0 with the elements of
a seeded subset, drawn anew for each k against version 0, moved by one ulp
(the lowest bit flipped). The bits depend on the global position only, so
any slice of any layout is made directly, and the reference regenerates
whatever version it needs without the versions between.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deployment import Slice

_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def key32(*words: int) -> int:
    """A 32-bit key from any whole numbers (a seed past 2**32 included)."""
    h = 0
    for w in words:
        h = _splitmix64(h ^ (w & _M64) ^ ((w >> 64) & _M64))
    return h & 0xFFFFFFFF


def _fmix32(h):
    """MurmurHash3's 32-bit finalizer, on uint32 arrays."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def flip_threshold(fraction: float) -> int:
    return min(int(round(fraction * 2**32)), 2**32 - 1)


@functools.partial(jax.jit, static_argnums=0)
def _bits(shape, gcols, off0, off1, kbase, kflip, thresh):
    u32 = jnp.uint32
    r = jnp.arange(shape[0], dtype=u32)[:, None] + off0
    c = jnp.arange(shape[1], dtype=u32)[None, :] + off1
    idx = r * gcols + c
    base = (_fmix32(idx * u32(0x9E3779B9) + kbase) & u32(0x83FF)) | u32(0x3800)
    flip = (_fmix32(idx * u32(0x85EBCA77) + kflip) < thresh).astype(u32)
    return (base ^ flip).astype(jnp.uint16)


def _as2d(s: Slice) -> Tuple[Tuple[int, int], int, int, int]:
    """(local 2-D shape, global columns, row offset, column offset)."""
    if len(s.gshape) == 1:
        return (1, s.shape[0]), s.gshape[0], 0, s.offset[0]
    if len(s.gshape) != 2:
        raise ValueError(f"{s.name}: only 1-D and 2-D tensors are made")
    return s.shape, s.gshape[1], s.offset[0], s.offset[1]


def device_bits(s: Slice, seed: int, version: int, fraction: float, device=None):
    """The uint16 bits of one block at ``version``, made on ``device``
    (JAX's default device where none is given)."""
    shape, gcols, off0, off1 = _as2d(s)
    thresh = flip_threshold(fraction) if version else 0
    args = [
        np.uint32(v)
        for v in (gcols, off0, off1, key32(seed, s.index), key32(seed, s.index, version), thresh)
    ]
    if device is not None:
        args = jax.device_put(args, device)
    return _bits(shape, *args)


def host_device():
    """JAX's CPU device: the host makes bits several times faster than a
    TPU v5e hands them back (0.68 GB/s measured through ``np.asarray``)."""
    return jax.devices("cpu")[0]


def host_blocks(
    slices: List[Slice], seed: int, version: int, fraction: float, ahead: int = 4
) -> Iterator[Tuple[Slice, np.ndarray]]:
    """Each block's uint16 bits on the host, made by XLA on the host's
    cores ``ahead`` blocks in advance."""
    cpu = host_device()
    pending = []
    for s in slices:
        pending.append((s, device_bits(s, seed, version, fraction, cpu)))
        if len(pending) > ahead:
            t, arr = pending.pop(0)
            yield t, np.asarray(arr).reshape(t.shape)
    for t, arr in pending:
        yield t, np.asarray(arr).reshape(t.shape)


@jax.jit
def digest(x):
    """Two 32-bit sums over a tensor's 16-bit words, one weighted by
    position: a device-side check of landed bytes (wrapping arithmetic, so
    the order of the sum does not matter)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint16).reshape(-1).astype(jnp.uint32)
    i = jnp.arange(u.size, dtype=jnp.uint32)
    return jnp.stack([jnp.sum(u * (2 * i + 1)), jnp.sum(_fmix32(u ^ (i * jnp.uint32(0x9E3779B9))))])
