"""Device time per job of collective ops during which no other op runs on
that device; the chip with the most.  Nothing to read without collectives."""


def read(ctx):
    devs = [d for d in ctx.devices if d.collective]
    if not devs or ctx.jobs == 0:
        return None
    return max(d.collective_exposed for d in devs) / ctx.jobs / 1e6
