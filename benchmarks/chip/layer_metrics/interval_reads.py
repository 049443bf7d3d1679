"""Resharded transfer plane, work done: interval reads per update, the
sum of the ``intervals`` attribute of the program's ``fetch_unit`` spans
(one span per destination unit; each interval read makes two checksums
and one progress call, and has no span of its own)."""

import spans


def read(ctx):
    got = spans.mean_attr_sum(ctx, "fetch_unit", "intervals")
    return None if got is None else (got, "reads")
