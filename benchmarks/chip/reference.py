"""The plain reference's control: int8 round trips, computed plainly.

It imports nothing of the program. Over a same-datacenter link the wire is
raw, so a rollout holds exactly the version published (``gen`` makes it
from the seed). The control puts in the program's place the same bytes in
the next precision down, each tensor's int8 round trip:

- rows of 256 elements (the last one zero-padded);
- per row ``scale = max(absmax * f32(1/127), f32(1e-12))`` in float32;
- ``q`` the integer nearest ``x / scale`` (ties to even), clipped to
  +-127; the nearest integer is found in float64, where the quotient of
  two float32 values lies close enough to the exact one that the rounding
  cannot come out otherwise;
- the decoded value ``bf16(f32(q) * scale)``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

ROW = 256
#: elements quantized at a time (a whole number of rows), so the float64
#: temporaries stay small however large a tensor is
BLOCK = 1 << 20
INT8 = 127


def bf16_to_f32(bits: np.ndarray) -> np.ndarray:
    return (bits.astype(np.uint32) << 16).view(np.float32)


def f32_to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 bits, to nearest, ties to even (finite x)."""
    b = x.view(np.uint32)
    return ((b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) >> 16).astype(np.uint16)


def _rows(x: np.ndarray) -> np.ndarray:
    pad = (-x.size) % ROW
    if pad:
        x = np.concatenate([x, np.zeros(pad, x.dtype)])
    return x.reshape(-1, ROW)


def quantize(rows: np.ndarray, qmax: int) -> Tuple[np.ndarray, np.ndarray]:
    """float32 rows -> (q as int8, scale per row)."""
    absmax = np.max(np.abs(rows), axis=1)
    scale = np.maximum(absmax * np.float32(1 / qmax), np.float32(1e-12))
    q = np.rint(rows.astype(np.float64) / scale.astype(np.float64)[:, None])
    return np.clip(q, -qmax, qmax).astype(np.int8), scale


def _round_trip(bits: np.ndarray, qmax: int) -> np.ndarray:
    q, scale = quantize(_rows(bf16_to_f32(bits)), qmax)
    return f32_to_bf16((q.astype(np.float32) * scale[:, None]).reshape(-1)[: bits.size])


def round_trip(bits: np.ndarray, qmax: int = INT8) -> np.ndarray:
    """The bytes of one flat run of bf16 bits after an int8 round trip,
    ``BLOCK`` elements at a time (rows never straddle two blocks)."""
    out = np.empty_like(bits)
    for a in range(0, bits.size, BLOCK):
        out[a : a + BLOCK] = _round_trip(bits[a : a + BLOCK], qmax)
    return out
