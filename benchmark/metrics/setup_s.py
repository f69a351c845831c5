"""Process start to the first timed job: backend start, the device image,
compile or cache load, and the warm-up job of the cell's own shapes."""


def read(ctx):
    return ctx.setup_s
