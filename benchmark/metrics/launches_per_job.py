"""Device program launches per job on each chip: the ``XLA Modules``
events (one per launch) that overlap the traced window, averaged over
the trace's chips, over the program's ``pctpu.run_planar`` calls in the
window (one a job).  Overlap, not containment: the device planes' clock
can read a fraction of a millisecond behind the host's, so the first
job's launch may seem to start before the window.  None where the trace
has no device planes or the program no such span."""

from benchmark import program_trace


def read(ctx):
    calls = len(program_trace.spans(ctx, "pctpu.run_planar"))
    if not ctx.trace.devices or calls == 0:
        return None
    launches = sum(1 for d in ctx.trace.devices for m in d.modules
                   if m.end > ctx.lo and m.start < ctx.hi)
    return launches / len(ctx.trace.devices) / calls
