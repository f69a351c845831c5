"""Halo bytes per job, in MB (1e6 B): the ``halo_bytes`` argument of the
program's ``pctpu.step.dispatch`` spans in the window (what the iterate
runner's halo exchanges built, over the whole mesh), over the jobs."""

from benchmark import program_trace


def read(ctx):
    spans = [s for s in program_trace.spans(ctx, "pctpu.step.dispatch")
             if "halo_bytes" in s.args]
    if not spans or ctx.jobs == 0:
        return None
    return sum(s.args["halo_bytes"] for s in spans) / ctx.jobs / 1e6
