"""Codec and repack decode: the program's counter ``stall/decode`` per
update, in thread-seconds. Nothing to read where no update decoded."""


def read(ctx):
    ups = ctx["updates"]
    if "ctr:stall/decode" not in ups[0]:
        return None
    total = sum(u["ctr:stall/decode"] for u in ups)
    return (total / len(ups), "s") if total > 0 else None
