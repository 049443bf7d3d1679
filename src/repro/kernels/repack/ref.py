"""NumPy oracles for the repack gather kernel."""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def repack_ref(
    staging: np.ndarray,
    instructions: Sequence[Tuple[int, int, int]],
    out_nbytes: int,
) -> np.ndarray:
    """Instruction-level NumPy oracle: scatter each ``(staging_offset,
    out_offset, nbytes)`` run; uncovered output bytes are zero. Delegates
    to the production scatter path so the kernel parity tests validate
    the exact reference implementation the executor ships."""
    from repro.resharding.executor import repack_np

    return repack_np(np.asarray(staging, dtype=np.uint8), list(instructions), out_nbytes)


def random_instructions(
    rng: np.random.Generator, out_nbytes: int, max_runs: int = 12
) -> List[Tuple[int, int, int]]:
    """Random exact tiling of [0, out_nbytes) for parity tests: cut the
    output into runs, each sourced from a distinct staging range (staging
    is the runs concatenated in shuffled order)."""
    n_runs = int(rng.integers(1, max_runs + 1))
    cuts = sorted(
        set([0, out_nbytes]) | set(rng.integers(1, max(2, out_nbytes), n_runs))
    )
    runs = [(a, b - a) for a, b in zip(cuts[:-1], cuts[1:])]
    order = rng.permutation(len(runs))
    instructions = []
    pos = 0
    for k in order:
        d_off, nbytes = runs[k]
        instructions.append((pos, d_off, nbytes))
        pos += nbytes
    return instructions
