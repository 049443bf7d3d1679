"""Landing in HBM: ``land_rollout`` per update (``device_put`` and
``block_until_ready`` of every tensor), host clock, the largest over
replicas."""


def read(ctx):
    ups = ctx["updates"]
    return sum(u["land_s"] for u in ups) / len(ups), "s"
