"""Host-facing repack entry points: gather-map building + device gather."""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.repack.kernel import GATHER_WINDOW, gather_bytes

#: a contiguous run ``(src_start, out_start, count)``: output positions
#: ``[out_start, out_start + count)`` take source positions from
#: ``src_start`` on
Run = Tuple[int, int, int]


def build_gather_map(
    runs: Sequence[Run], start: int, stop: int, fill: int
) -> np.ndarray:
    """int32[stop - start] mapping output positions ``[start, stop)`` to
    source positions; positions no run covers map to ``fill``."""
    idx = np.full(stop - start, fill, dtype=np.int32)
    for src, out, count in runs:
        lo, hi = max(out, start), min(out + count, stop)
        if lo < hi:
            idx[lo - start : hi - start] = np.arange(
                src + lo - out, src + hi - out, dtype=np.int32
            )
    return idx


def gather_windows(
    runs: Sequence[Run],
    out: np.ndarray,
    fill: int,
    gather: Callable[[jax.Array], jax.Array],
) -> np.ndarray:
    """Fill ``out`` (flat) window by window: ``gather(idx)`` runs on the
    device for each window's index map. Every window has the same length
    (the last one is padded with ``fill``), so one program serves them
    all."""
    n = out.shape[0]
    w = min(GATHER_WINDOW, n)
    for w0 in range(0, n, w):
        w1 = min(w0 + w, n)
        idx = build_gather_map(runs, w0, w0 + w, fill)
        out[w0:w1] = np.asarray(gather(jnp.asarray(idx)))[: w1 - w0]
    return out


def repack_bytes(
    staging: np.ndarray,
    instructions: Sequence[Run],
    out_nbytes: int,
) -> np.ndarray:
    """Device repack: assemble the destination unit payload (uint8
    [out_nbytes]) from the staging buffer via the device gather. Output
    bytes no instruction covers read the zero byte appended to staging,
    matching the NumPy reference."""
    flat = np.asarray(staging, dtype=np.uint8).reshape(-1)
    # the device gather reads out-of-range indices silently: refuse them
    for s_off, d_off, nbytes in instructions:
        if d_off < 0 or d_off + nbytes > out_nbytes:
            raise ValueError(f"instruction out of range: {(s_off, d_off, nbytes)}")
        if s_off < 0 or s_off + nbytes > flat.shape[0]:
            raise ValueError(f"staging read out of range: {(s_off, d_off, nbytes)}")
    dev = jnp.asarray(np.concatenate([flat, np.zeros(1, np.uint8)]))
    return gather_windows(
        instructions,
        np.empty(out_nbytes, np.uint8),
        flat.shape[0],
        lambda idx: gather_bytes(dev, idx),
    )
