"""Host time of one ``run_planar`` call until it returns (enqueue plus the
entry's synchronous host work), from the benchmark's ``bench.run_planar``
spans on the trace's clock; the mean over the window's jobs."""

from benchmark import trace_reduce


def read(ctx):
    spans = trace_reduce.host_spans(ctx.trace, "bench.run_planar",
                                    ctx.lo, ctx.hi)
    if not spans:
        return None
    return sum(s.dur for s in spans) / len(spans) / 1e6
