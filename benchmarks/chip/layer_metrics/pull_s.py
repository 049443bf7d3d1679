"""Transfer plane: the rollouts' ``update("latest")`` per update, host
clock, the largest over replicas."""


def read(ctx):
    ups = ctx["updates"]
    return sum(u["pull_s"] for u in ups) / len(ups), "s"
