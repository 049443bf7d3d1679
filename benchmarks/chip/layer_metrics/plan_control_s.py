"""Control and planning: the program's counters ``stall/plan_wait`` and
``stall/control`` per update (seconds, summed over the threads that
waited on a plan or a server call)."""


def read(ctx):
    ups = ctx["updates"]
    if "ctr:stall/control" not in ups[0]:
        return None
    total = sum(u["ctr:stall/plan_wait"] + u["ctr:stall/control"] for u in ups)
    return total / len(ups), "s"
