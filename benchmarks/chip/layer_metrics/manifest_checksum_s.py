"""Publish-time checksums: wall seconds per update inside the program's
``manifest`` spans (a checksummed ``build_manifest``: each trainer shard's
publish, and a resharded rollout's manifest after its pull), overlapping
spans counted once."""

import spans


def read(ctx):
    got = spans.mean_wall_seconds(ctx, ("manifest",))
    return None if got is None else (got, "s")
