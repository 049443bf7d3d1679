"""Delta-base snapshots: wall seconds per update inside the program's
``snapshot_base`` spans (each trainer shard's at ``unpublish``, each
rollout's before its pull), overlapping spans counted once."""

import spans


def read(ctx):
    got = spans.mean_wall_seconds(ctx, ("snapshot_base",))
    return None if got is None else (got, "s")
