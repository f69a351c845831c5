"""Device time per job of the iterate program's non-kernel ops under the
named scope ``pctpu.kernel_io`` (the Pallas wrappers' tile-alignment pad,
crop and band concatenation), averaged over the chips: a part of
``step_glue_ms``."""

from benchmark import program_trace


def read(ctx):
    return program_trace.scoped_ms(ctx, "pctpu.kernel_io")
