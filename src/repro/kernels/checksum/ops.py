"""Jitted wrapper: checksum arbitrary tensors on-device."""

from __future__ import annotations

import functools

import jax
import numpy as np

from repro.kernels.checksum.kernel import checksum_tensor


@functools.partial(jax.jit, static_argnames=("interpret",))
def tensor_checksum(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Returns uint32[2] = (s1, s2) over the tensor's little-endian bytes,
    matching ``repro.transfer.checksum.checksum`` (fold64 combines them).
    The kernel reads the tensor in its own dtype and shape: no copy."""
    return checksum_tensor(x, interpret=interpret)


def host_equivalent(x) -> int:
    """Host-side value this kernel must match (for tests)."""
    from repro.transfer.checksum import checksum

    return checksum(np.asarray(x))
