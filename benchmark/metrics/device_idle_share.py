"""Share of the traced window in which no op ran on the device: 1 minus the
union of device op intervals over the window, averaged over the chips."""


def read(ctx):
    if not ctx.devices:
        return None
    busy = sum(d.busy / d.window for d in ctx.devices) / len(ctx.devices)
    return 100.0 * (1.0 - busy)
