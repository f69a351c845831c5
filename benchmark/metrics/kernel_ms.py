"""Device time of the stencil kernels per job: the summed durations of the
Mosaic custom calls (``tpu_custom_call``) inside the iterate program, per
job, averaged over the chips."""


def read(ctx):
    devs = [d for d in ctx.devices if d.kernel_launches]
    if not devs or ctx.jobs == 0:
        return None
    return sum(d.kernel for d in devs) / len(devs) / ctx.jobs / 1e6
