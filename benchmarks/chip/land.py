"""Landing a pulled version in the rollout chip's HBM.

The program's pulls end in host memory; a serving engine then copies the
weights into HBM. ``land_rollout`` is that copy, and the single place to
re-point once the program lands pulls in HBM itself.
"""

from __future__ import annotations

from typing import Dict

import jax


def land_rollout(handle, device, landed: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Copy every tensor of ``handle``'s store onto ``device``, one at a
    time, swapping in place: the new array is made and ready before the
    old one of the same name is deleted, as an engine swapping weights
    under a live KV cache would. Returns ``landed``, updated."""
    for name, arr in handle.store.tensors().items():
        new = jax.device_put(arr, device)
        new.block_until_ready()
        old = landed.get(name)
        landed[name] = new
        if old is not None:
            old.delete()
    return landed
