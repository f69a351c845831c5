"""Device time per job of every op of the iterate program that is not a
stencil kernel: pad, concatenate, slices, casts, collective-permutes.
Control-flow containers (``while``) are not added.  Averaged over chips."""


def read(ctx):
    devs = [d for d in ctx.devices if d.has_program]
    if not devs or ctx.jobs == 0:
        return None
    return sum(d.glue for d in devs) / len(devs) / ctx.jobs / 1e6
