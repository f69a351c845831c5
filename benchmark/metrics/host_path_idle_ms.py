"""Device idle time per job while the host is inside the program's own
``pctpu.run_planar`` span: each chip's idle intervals in the window,
intersected with those spans, per job, averaged over the chips."""

from benchmark import program_trace


def read(ctx):
    return program_trace.idle_inside_ms(ctx, "pctpu.run_planar")
