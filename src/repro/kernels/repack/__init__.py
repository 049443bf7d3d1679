"""Gather/repack kernel for cross-layout resharding (see kernel.py)."""

from repro.kernels.repack.kernel import gather_bytes
from repro.kernels.repack.ops import build_gather_map, repack_bytes
from repro.kernels.repack.ref import random_instructions, repack_ref

__all__ = [
    "build_gather_map",
    "gather_bytes",
    "random_instructions",
    "repack_bytes",
    "repack_ref",
]
