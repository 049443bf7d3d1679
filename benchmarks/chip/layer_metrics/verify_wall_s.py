"""Host checksums, wall time: seconds per update inside the program's
``verify`` spans (every checksum of a unit pull), overlapping spans of
the pull threads counted once; ``verify_thread_s`` sums the same work
over threads."""

import spans


def read(ctx):
    got = spans.mean_wall_seconds(ctx, ("verify",))
    return None if got is None else (got, "s")
