"""Pure-jnp oracle for int8 block quantization."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def round_quotient(x: jax.Array, s: jax.Array) -> jax.Array:
    """The integer nearest the exact quotient x / s, ties to even, as f32
    (x, s f32 and broadcastable; s positive and normal).

    The f32 division only proposes the answer; exact comparisons settle
    it, so a division that is off by an ulp on some backend changes no
    bit. (The quotient's own f32 rounding can land on a half and flip a
    plain ``round(x / s)``; this rule cannot.) Exactness: ``t = q +- 0.5``
    has at most 9 significant bits and ``s`` is split into two 12-bit
    halves, so ``t * hi`` and ``t * lo`` are exact, and ``x - t * hi`` is
    exact wherever the comparison is close (Sterbenz).
    """
    q = jnp.round(x / s)
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(s, jnp.int32) & -4096, jnp.float32
    )
    lo = s - hi
    for half in (0.5, -0.5):
        t = q + half
        a, b = x - t * hi, t * lo
        past = a > b if half > 0 else a < b
        odd = (q.astype(jnp.int32) & 1) != 0
        q = jnp.where(past | ((a == b) & odd), q + 2 * half, q)
    return q


def quantize_ref(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8: x [R, C] -> (q int8 [R, C], scale f32 [R]).

    The wire codec's scheme, op for op with its NumPy reference
    (``Int8Codec._quant_rows``): the scale is a multiply by f32(1/127),
    never a division, and q is :func:`round_quotient`'s exact rounding.
    """
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(absmax * jnp.float32(1 / 127), 1e-12)
    q = round_quotient(x, scale[:, None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_ref(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale[:, None]).astype(dtype)
