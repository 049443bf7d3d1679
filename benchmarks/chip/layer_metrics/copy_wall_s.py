"""Transfer-plane copies: wall seconds per update inside the program's
``wire_copy`` (source read and the copy) and ``write`` (into the rollout's
buffers) spans of the raw unit plane, overlapping spans counted once."""

import spans


def read(ctx):
    got = spans.mean_wall_seconds(ctx, ("wire_copy", "write"))
    return None if got is None else (got, "s")
