"""Striped-read repack as a jitted device gather.

Cross-layout resharding (``repro.resharding``) lands interval payloads
from many source shards in a contiguous staging buffer; the repack step
permutes those bytes into the destination transfer unit's layout. On the
device this is a gather: an int32 index map (built on host from the
plan's instructions, ``ops.build_gather_map``) maps every output byte to
its staging position. Byte granularity is the general case — intervals
of bf16 tensors can land on 2-byte alignment, so a word-level gather
cannot assume 4-byte-aligned runs.

The gather is an XLA gather, not a Pallas kernel: Mosaic lowers only
gathers within one 2D block along one axis (``tpu.dynamic_gather``), so
an arbitrary byte permutation of a unit that does not fit VMEM has no
Pallas form. XLA tiles the gather over HBM itself, and the same program
runs on the CPU backend in tests. The output is produced in windows of
``GATHER_WINDOW`` positions: a map of one int32 per output byte of a
whole 1.88 GB unit would take 7.5 GB of HBM, and the TPU compiler copies
it once more.
"""

from __future__ import annotations

import jax

#: output positions per gather call: the index map of one call is 256 MiB
GATHER_WINDOW = 64 << 20


@jax.jit
def gather_bytes(staging: jax.Array, idx: jax.Array) -> jax.Array:
    """staging: uint8[S], idx: int32[N] with every entry in [0, S) ->
    uint8[N] = staging[idx]."""
    return staging.at[idx].get(mode="promise_in_bounds")
