"""End-to-end transfer checksums (4.6).

TensorHub attaches a per-unit checksum to every published reference and
validates it after transfer. We use a position-weighted Fletcher-style fold
over 32-bit words:

    s1 = sum(w_i)                 mod 2^32
    s2 = sum(((i & 0xffff)+1) * w_i) mod 2^32
    checksum = (s2 << 32) | s1

The position weight catches reordering/offset bugs that a plain sum misses.
A non-empty buffer whose fold lands on exactly 0 is remapped to
:data:`ZERO_STANDIN`: the transfer layer uses checksum 0 as the
"verification disabled" sentinel (divergent-manifest pulls), and a
colliding real payload — e.g. symmetric constant data whose weighted
sums cancel — must not silently disarm end-to-end verification.
All arithmetic is mod-2^32, so the *same* value is computed by

* this NumPy implementation (host side, used by the real transport),
* the pure-jnp oracle ``repro.kernels.checksum.ref`` (int32 wraparound), and
* the Pallas TPU kernel ``repro.kernels.checksum`` (device side, overlappable
  with the RDMA transfer, per 4.6).
"""

from __future__ import annotations

import numpy as np

#: words folded per pass. The fold's uint64 temporaries stay a few MiB
#: whatever the buffer (a whole-buffer pass would hold 8 bytes of them
#: per payload byte: 15 GB for a 1.88 GB unit). A multiple of 65536, so
#: the position weights repeat block by block.
_BLOCK_WORDS = 1 << 20
_WEIGHTS = (np.arange(_BLOCK_WORDS, dtype=np.uint64) & np.uint64(0xFFFF)) + np.uint64(1)

#: stand-in for a non-empty buffer folding to exactly 0 — any fixed
#: non-zero value works (the induced collision class is the same
#: ~2^-64 as the fold itself); shared with ``kernels.checksum.fold64``
ZERO_STANDIN = 0x5EED_0000_0000_5EED


def _as_words(buf: bytes | bytearray | memoryview | np.ndarray) -> np.ndarray:
    if isinstance(buf, np.ndarray):
        raw = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
    else:
        raw = np.frombuffer(buf, dtype=np.uint8)
    pad = (-raw.size) % 4
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, dtype=np.uint8)])
    return raw.view(np.uint32)


def checksum(buf: bytes | bytearray | memoryview | np.ndarray) -> int:
    """64-bit fold checksum of a byte buffer (see module docstring)."""
    words = _as_words(buf)
    if words.size == 0:
        return 0
    s1 = s2 = 0
    for b0 in range(0, words.size, _BLOCK_WORDS):
        w = words[b0 : b0 + _BLOCK_WORDS].astype(np.uint64)
        # uint64 sums wrap mod 2^64, which keeps them exact mod 2^32
        s1 += int(w.sum(dtype=np.uint64))
        s2 += int((w * _WEIGHTS[: w.size]).sum(dtype=np.uint64))
    s1, s2 = s1 & 0xFFFFFFFF, s2 & 0xFFFFFFFF
    return ((s2 << 32) | s1) or ZERO_STANDIN


def combine(chunks: list[int]) -> int:
    """Order-sensitive combination of per-chunk checksums (for chunked
    verification paths): a second-level fold over the chunk checksums."""
    acc = np.uint64(0)
    for i, c in enumerate(chunks):
        w = np.uint64(c & 0xFFFFFFFFFFFFFFFF)
        acc = (acc + (np.uint64((i & 0xFFFF) + 1) * (w ^ (w >> np.uint64(32))))) & np.uint64(
            0xFFFFFFFFFFFFFFFF
        )
    return int(acc)
