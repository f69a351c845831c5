"""Pixel-iterations per second per chip: every job completed in the window
(C * H * W * iters each), over the window's seconds, over the chips."""


def read(ctx):
    if ctx.jobs == 0 or ctx.window_s <= 0:
        return None
    work = ctx.jobs * ctx.pixels_per_job * ctx.iters
    return work / ctx.window_s / ctx.cell.chips / 1e9
