"""Device time per job of the iterate program's non-kernel ops under the
named scope ``pctpu.halo`` (ghost pads, concatenates, collective-permutes),
averaged over the chips: a part of ``step_glue_ms``."""

from benchmark import program_trace


def read(ctx):
    return program_trace.scoped_ms(ctx, "pctpu.halo")
