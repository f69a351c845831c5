"""Remote-DMA wire bytes per job, in MB (1e6 B): the ``rdma_wire_bytes``
argument of the program's ``pctpu.step.dispatch`` spans in the window
(what the remote-DMA kernels' band copies moved over the whole mesh:
whole tile-aligned bands, where ``halo_mb_per_job`` counts the ghosts
inside them), over the jobs.  None where no span carries it."""

from benchmark import program_trace


def read(ctx):
    spans = [s for s in program_trace.spans(ctx, "pctpu.step.dispatch")
             if "rdma_wire_bytes" in s.args]
    if not spans or ctx.jobs == 0:
        return None
    return sum(s.args["rdma_wire_bytes"] for s in spans) / ctx.jobs / 1e6
