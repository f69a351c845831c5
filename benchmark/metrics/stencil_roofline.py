"""The stencil kernels' share of their roofline: the least time the chip
could take for one job's work on one chip, over ``kernel_ms``.

The work is counted from shapes (``work.py``): 2 operations per tap per
pixel-iteration of the direct stencil, and one u8 read and one u8 write of
the image per job; the peaks are ``peaks.json``'s for this device kind.
The VPU has no published peak, so against the bf16 MXU peak this reads low
by construction; it is there to move."""

from benchmark import work


def read(ctx):
    devs = [d for d in ctx.devices if d.kernel_launches]
    if not devs or ctx.jobs == 0 or ctx.peak is None:
        return None
    kernel_s = sum(d.kernel for d in devs) / len(devs) / ctx.jobs / 1e9
    taps = sum(len(row) for row in ctx.cell.config["weights"])
    pixels = ctx.pixels_per_job / ctx.cell.chips
    least, _ = work.least_time_s(work.stencil_ops(taps, pixels, ctx.iters),
                                 work.image_bytes(pixels), ctx.peak)
    return 100.0 * least / kernel_s
