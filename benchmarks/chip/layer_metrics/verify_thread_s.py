"""Host checksums: the program's counter ``stall/verify`` per update.
Thread-seconds: up to four reads verify at once, so this is busy time
summed over threads, not wall time."""


def read(ctx):
    ups = ctx["updates"]
    if "ctr:stall/verify" not in ups[0]:
        return None
    return sum(u["ctr:stall/verify"] for u in ups) / len(ups), "s"
