"""Fused dequant + gather: int8 wire frames -> repacked unit payload.

The staged reshard decode path materializes every interval twice: decode
the int8 frame into a staging buffer, then repack (gather) staging bytes
into the destination unit's layout. This module fuses the two — one
jitted device program reads the quantized rows and per-row scales of
*all* frames of one destination unit and writes dequantized elements
directly at their repacked positions:

    out[i] = (q[idx[i]] * scales[idx[i] // row_len]).astype(out_dtype)

``idx`` is an int32 element map, built on host from the plan's
placements window by window (``repack.build_gather_map`` in element
space); the row-grid ``lead``/``tail`` widening is never mapped, so the
trimmed elements are never decoded.

The kernel path requires every quantized frame of the unit to share one
TPU-friendly element dtype (f32/bf16/f16) and element-aligned
placements; anything else — mixed dtypes, f64, passthrough-only units —
takes :func:`fused_repack_np`, the NumPy fusion of the same two passes
(decode rows straight into the output span, no staging buffer).
``ReshardExecutor`` picks the path per unit and counts each choice in
telemetry (``decode/kernel_units``, ``decode/host_units``). Both
paths are bit-identical to staged decode-then-repack: the dequant math
is exactly ``Int8Codec.decode``'s (f32 multiply, round-to-nearest-even
downcast), and parity is pinned by tests.

Frames arrive parsed (:func:`repro.transfer.codec.parse_int8_frame`), so
header/scale/shape validation happened exactly once, at the transport
boundary.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.meta import dtype_from_str
from repro.kernels.repack.ops import Run, gather_windows

#: dtypes the device kernel handles (min-tile-friendly; f64 stays on host)
_KERNEL_DTYPES = ("float32", "bfloat16", "float16")

#: placement of one parsed frame in the destination unit payload:
#: (frame, lead, nbytes, unit_offset) — write frame bytes
#: [lead, lead + nbytes) at out[unit_offset : unit_offset + nbytes]
Placement = Tuple[object, int, int, int]


def _dequant_span(frame, lead: int, nbytes: int) -> np.ndarray:
    """Dequantize exactly the rows of ``frame`` that cover byte span
    [lead, lead + nbytes) and return those bytes — the NumPy half of the
    fusion (no whole-frame staging decode)."""
    npdtype = dtype_from_str(frame.dtype)
    isz = npdtype.itemsize
    rb = frame.row_len * isz
    r0 = lead // rb
    r1 = -(-(lead + nbytes) // rb)
    n = frame.nbytes // isz  # true element count of the frame
    e0 = r0 * frame.row_len
    e1 = min(r1 * frame.row_len, n)
    cnt = e1 - e0
    if cnt == (r1 - r0) * frame.row_len:
        qv = frame.q[e0:e1]  # full rows: no ragged-tail pad needed
    else:
        qv = np.zeros((r1 - r0) * frame.row_len, np.int8)
        qv[:cnt] = frame.q[e0:e1]
    x = qv.reshape(r1 - r0, frame.row_len).astype(np.float32)
    x *= frame.scales[r0:r1, None]  # in-place: same f32 multiply, one pass
    x = x.reshape(-1)[:cnt]
    if npdtype != np.float32:
        x = x.astype(npdtype)
    dec = np.ascontiguousarray(x).view(np.uint8)
    off = lead - r0 * rb
    return dec[off : off + nbytes]


def fused_repack_np(
    placements: Sequence[Placement], out_nbytes: int
) -> np.ndarray:
    """NumPy fused reference: each frame's covered rows dequantize
    straight into their repacked output span — one pass, no staging
    buffer, no decode-then-discard of the row-grid widening."""
    out = np.zeros(out_nbytes, dtype=np.uint8)
    for frame, lead, nbytes, uo in placements:
        if nbytes <= 0:
            continue
        if frame.is_passthrough:
            out[uo : uo + nbytes] = frame.passthrough[lead : lead + nbytes]
        else:
            out[uo : uo + nbytes] = _dequant_span(frame, lead, nbytes)
    return out


def kernel_dtype(placements: Sequence[Placement], out_nbytes: int) -> Optional[str]:
    """The single element dtype the device kernel would run at, or
    ``None`` when this unit must take the NumPy path (mixed/unsupported
    dtypes or row lengths, element-misaligned placements, nothing
    quantized)."""
    dtype: Optional[str] = None
    row_len: Optional[int] = None
    for frame, lead, nbytes, uo in placements:
        if frame.is_passthrough:
            continue
        if frame.dtype not in _KERNEL_DTYPES:
            return None
        if dtype is None:
            dtype, row_len = frame.dtype, frame.row_len
        elif frame.dtype != dtype or frame.row_len != row_len:
            return None
        isz = dtype_from_str(dtype).itemsize
        if lead % isz or nbytes % isz or uo % isz:
            return None
    if dtype is not None and out_nbytes % dtype_from_str(dtype).itemsize:
        return None
    return dtype


def build_elem_map(
    placements: Sequence[Placement], out_nbytes: int, dtype: str
) -> Tuple[np.ndarray, np.ndarray, List[Run], int, int]:
    """Host-side inputs of the device kernel: lay every quantized frame's
    values out on whole rows (a ragged last row is zero-padded), so
    element ``i`` of ``qcat`` has the scale ``scat[i // row_len]``, and
    describe each placement as an element run into the output. Returns
    ``(qcat, scat, runs, fill, row_len)``; output elements no run covers
    (gaps, passthrough spans overlaid later) map to ``fill``, the first
    element of an appended all-zero row, and decode to 0.0."""
    isz = dtype_from_str(dtype).itemsize
    quantized = [
        p for p in placements if not p[0].is_passthrough and p[2] > 0
    ]
    row_len = quantized[0][0].row_len
    rows = sum(frame.scales.size for frame, _, _, _ in quantized)
    if (rows + 1) * row_len > np.iinfo(np.int32).max:
        raise ValueError(f"unit of {rows} rows overflows the int32 element map")
    qcat = np.zeros((rows + 1) * row_len, np.int8)
    scat = np.zeros(rows + 1, np.float32)
    runs: List[Run] = []
    r0 = 0
    for frame, lead, nbytes, uo in quantized:
        q0 = r0 * row_len
        qcat[q0 : q0 + frame.q.size] = frame.q
        scat[r0 : r0 + frame.scales.size] = frame.scales
        runs.append((q0 + lead // isz, uo // isz, nbytes // isz))
        r0 += frame.scales.size
    return qcat, scat, runs, rows * row_len, row_len


@functools.partial(jax.jit, static_argnames=("row_len", "out_dtype"))
def dequant_gather(q, scales, idx, *, row_len: int, out_dtype: str):
    """The fused device decode of one output window: ``out[i] =
    (q[idx[i]] * scales[idx[i] // row_len]).astype(out_dtype)`` — the
    same f32 multiply and round-to-nearest-even downcast as the NumPy
    path. An XLA gather, not a Pallas kernel: Mosaic lowers only gathers
    within one 2D block, and a unit's frames do not fit VMEM (see
    ``repro.kernels.repack.kernel``)."""
    vals = q.at[idx].get(mode="promise_in_bounds").astype(jnp.float32)
    scale = scales.at[idx // row_len].get(mode="promise_in_bounds")
    return (vals * scale).astype(dtype_from_str(out_dtype))


def fused_repack(
    placements: Sequence[Placement], out_nbytes: int
) -> np.ndarray:
    """Device fused repack of one destination unit. The unit must be
    kernel-shaped (:func:`kernel_dtype` is not ``None``); the caller
    routes any other unit to :func:`fused_repack_np` and counts it."""
    dtype = kernel_dtype(placements, out_nbytes)
    if dtype is None:
        raise ValueError(
            "fused_repack: unit frames are not kernel-shaped (mixed or "
            "unsupported dtypes, misaligned placements, nothing quantized)"
        )
    qcat, scat, runs, fill, row_len = build_elem_map(
        placements, out_nbytes, dtype
    )
    q, s = jnp.asarray(qcat), jnp.asarray(scat)
    out = np.empty(out_nbytes, np.uint8)
    gather_windows(
        runs,
        out.view(dtype_from_str(dtype)),
        fill,
        lambda idx: dequant_gather(q, s, idx, row_len=row_len, out_dtype=dtype),
    )
    # passthrough frames (non-finite payloads, odd tails) overlay their
    # exact bytes after the kernel — byte-granular, like the NumPy path
    for frame, lead, nbytes, uo in placements:
        if frame.is_passthrough and nbytes > 0:
            out[uo : uo + nbytes] = frame.passthrough[lead : lead + nbytes]
    return out


__all__ = [
    "build_elem_map",
    "dequant_gather",
    "fused_repack",
    "fused_repack_np",
    "kernel_dtype",
]
