"""Host time per job inside the step layer: the summed durations of the
program's ``pctpu.step.*`` spans (prepare, build, dispatch, crop) in the
window, over the window's jobs."""

from benchmark import program_trace


def read(ctx):
    spans = program_trace.spans(ctx, prefix=program_trace.STEP_PREFIX)
    if not spans or ctx.jobs == 0:
        return None
    return sum(s.dur for s in spans) / ctx.jobs / 1e6
