"""Read the program's own spans and scopes from a traced run.

``trace_reduce.py`` classifies device ops by kind and keeps the
benchmark's ``bench.*`` host spans.  This module reads what the program
itself puts on the same trace:

- host spans named ``pctpu.*`` (``obs.trace.span``), with the arguments
  the program gave them: ``pctpu.run_planar``, ``pctpu.resolve``,
  ``pctpu.step.{build,dispatch}``; the dispatch span carries
  ``exchanges`` and ``halo_bytes``;
- the named scopes of the iterate executable's instructions
  (``pctpu.halo``, ``pctpu.kernel_io``): the device trace's op events
  carry only instruction names, so the map comes from the executable's
  compiled text (``ConvolutionModel.iterate_text`` and
  ``step.op_scopes``), built after the window in the traced run only.

A program without these spans or that method (an older commit) gives
empty readings, and every reader then returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import sys

from benchmark import trace_reduce

PREFIX = "pctpu."
STEP_PREFIX = "pctpu.step."


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: int  # ns, on the trace's clock
    end: int
    args: dict

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class ProgramTrace:
    spans: list[HostSpan]
    scopes: dict[str, str] | None = None  # instruction -> scope; lazy
    module: str = ""                      # the executable's module name


def load_spans(path: str) -> list[HostSpan]:
    """The ``pctpu.*`` host spans of an ``.xplane.pb`` (or a log dir
    holding one), sorted by start; [] where there is none."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        try:
            path = trace_reduce.find_xplane(path)
        except FileNotFoundError:
            return []
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    out.append(HostSpan(e.name, start,
                                        start + int(e.duration_ns),
                                        dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


def of(ctx) -> ProgramTrace:
    """The run's program trace, read once per run context."""
    pt = getattr(ctx, "program", None)
    if pt is None:
        from benchmark import run

        pt = ctx.program = ProgramTrace(load_spans(run.TRACE_DIR))
    return pt


def spans(ctx, name: str | None = None, prefix: str | None = None):
    """Program spans inside the traced window, by name or name prefix."""
    return [s for s in of(ctx).spans
            if s.start >= ctx.lo and s.end <= ctx.hi
            and (name is None or s.name == name)
            and (prefix is None or s.name.startswith(prefix))]


def build_scopes(cell, iters: int) -> tuple[dict[str, str], str]:
    """({instruction: scope}, module name) of the cell's iterate
    executable, from the program's own map; ({}, "") where the program
    has none."""
    from benchmark import run
    from parallel_convolution_tpu.models import ConvolutionModel
    from parallel_convolution_tpu.parallel import step
    from parallel_convolution_tpu.parallel.mesh import make_grid_mesh

    if not (hasattr(ConvolutionModel, "iterate_text")
            and hasattr(step, "op_scopes")):
        return {}, ""
    conf, plan = cell.config, cell.config["plan"]
    model = ConvolutionModel(
        filt=conf["filter"], mesh=make_grid_mesh(
            run.devices_for(cell), tuple(cell.traffic["mesh"])),
        backend=plan["backend"], storage=plan["storage"], fuse=plan["fuse"],
        quantize=conf["quantize"], boundary=conf["boundary"],
        fallback=False)
    text = model.iterate_text(run.image_shape(cell), iters)
    head = text.split("\n", 1)[0].split()
    module = head[1].rstrip(",") if head[:1] == ["HloModule"] else ""
    return step.op_scopes(text), module


def scopes(ctx) -> dict[str, str]:
    """The iterate executable's scope map, built on first use; {} where
    the program has none or it names another module than the trace's
    iterate program."""
    pt = of(ctx)
    if pt.scopes is None:
        try:
            pt.scopes, pt.module = build_scopes(ctx.cell, ctx.iters)
        except Exception as e:  # noqa: BLE001 — the metric goes silent
            print(f"program_trace: no scope map: {e!r}", file=sys.stderr)
            pt.scopes, pt.module = {}, ""
    for dev in ctx.trace.devices:
        wins = trace_reduce.program_windows(dev, ctx.lo, ctx.hi)
        if wins and pt.module and \
                trace_reduce.module_name(wins[0]) != pt.module:
            return {}
    return pt.scopes


def scoped_ms(ctx, scope: str) -> float | None:
    """Device time per job of the iterate program's non-kernel leaf ops
    whose instruction maps to ``scope``, averaged over the chips that ran
    the program (the chips ``step_glue_ms`` averages over)."""
    if not ctx.trace.devices or ctx.jobs == 0:
        return None
    smap = scopes(ctx)
    if scope not in smap.values():
        return None
    per_chip = []
    for dev in ctx.trace.devices:
        prog = trace_reduce.program_ops(dev, ctx.lo, ctx.hi)
        if prog:
            per_chip.append(sum(
                s.dur for s in prog if not trace_reduce.is_kernel(s)
                and smap.get(trace_reduce.op_name(s)) == scope))
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / ctx.jobs / 1e6


def intersect(a, b) -> list[tuple[int, int]]:
    """Parts of merged intervals ``a`` that merged ``b`` also covers."""
    return trace_reduce.subtract(a, trace_reduce.subtract(a, b))


def idle_inside_ms(ctx, name: str) -> float | None:
    """Device idle time per job while the host is inside spans ``name``:
    each chip's idle intervals in the window intersected with the spans,
    averaged over the trace's chips (those ``idle_by_host`` averages)."""
    host = spans(ctx, name)
    if not host or not ctx.trace.devices or ctx.jobs == 0:
        return None
    inside = trace_reduce.union((s.start, s.end) for s in host)
    idle = 0
    for dev in ctx.trace.devices:
        busy = trace_reduce.union(trace_reduce.within(dev.ops, ctx.lo,
                                                      ctx.hi))
        idle += trace_reduce.total(intersect(
            trace_reduce.gaps(busy, ctx.lo, ctx.hi), inside))
    return idle / len(ctx.trace.devices) / ctx.jobs / 1e6


def idle_by_span(ctx, n: int = 10):
    """[[program span, idle seconds per chip], ...]: each device gap in
    the window named by the innermost ``pctpu.*`` span covering its
    midpoint — which of the program's layers the chip waited on."""
    host = spans(ctx)
    starts = [h.start for h in host]
    acc: dict[str, list[int]] = {}
    for dev in ctx.trace.devices:
        busy = trace_reduce.union(trace_reduce.within(dev.ops, ctx.lo,
                                                      ctx.hi))
        for s, e in trace_reduce.gaps(busy, ctx.lo, ctx.hi):
            mid = (s + e) // 2
            i = bisect.bisect_right(starts, mid)
            # program spans nest a few deep: the innermost that covers
            # the midpoint is among the last few that start before it.
            inner = [h for h in host[max(0, i - 8):i] if mid < h.end]
            label = (min(inner, key=lambda h: h.dur).name if inner
                     else "outside program spans")
            slot = acc.setdefault(label, [0, 0])
            slot[0] += e - s
            slot[1] += 1
    ndev = max(1, len(ctx.trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{k} ({v[1]} gaps)", v[0] / ndev / 1e9] for k, v in ranked]
