"""Handle API, publish: the trainer's ``unpublish`` + ``publish`` per update,
host clock (the delta-base snapshot at ``unpublish`` and the publish-time
checksums included; the largest over trainer shards publishing at once)."""


def read(ctx):
    ups = ctx["updates"]
    return sum(u["publish_s"] for u in ups) / len(ups), "s"
