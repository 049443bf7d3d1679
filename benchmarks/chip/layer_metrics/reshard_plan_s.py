"""Client-side reshard planning: wall seconds per update inside the
program's ``plan_shard`` spans (``plan_shard`` and the executor's
construction), apart from the server calls ``plan_control_s`` counts."""

import spans


def read(ctx):
    got = spans.mean_wall_seconds(ctx, ("plan_shard",))
    return None if got is None else (got, "s")
