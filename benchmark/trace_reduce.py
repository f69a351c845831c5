"""Reduce a ``jax.profiler`` trace to device op intervals and host spans.

The yardstick every per-layer metric reads.  It keeps what the profiler
recorded and adds no model of its own:

- per device plane (``/device:TPU:N``): the ``XLA Modules`` line (one event
  per program launch) and the ``XLA Ops`` line (one event per HLO op);
- on the host: the benchmark's own ``bench.*`` spans
  (``jax.profiler.TraceAnnotation``), which sit on the trace's clock.

Control-flow containers (``while``, ``conditional``, ``call``) span their
own body ops, so an op that encloses another op of its line is a container
and never counts as busy time by itself.  Busy time is a union of
intervals, so nothing is counted twice.

Kernels are found by what they are, not by what they are called: a kernel
is a Mosaic custom call (``custom_call_target="tpu_custom_call"``) inside the
iterate program; the iterate program is the module name that holds the most
device time.  Collectives are found by their HLO opcode.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE = re.compile(
    r"\b(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(-start|-done)?\(")
_MODULE_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int  # ns, on the trace's clock
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: list[Span]
    ops: list[Span]         # leaf ops, containers removed
    containers: list[Span]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[Span]        # bench.* spans only


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _spans(line) -> list[Span]:
    return [Span(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def split_containers(ops: list[Span]) -> tuple[list[Span], list[Span]]:
    """(leaves, containers): a container encloses another op of its line."""
    ops = sorted(ops, key=lambda s: (s.start, -s.dur))
    leaves, containers = [], []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if (nxt is not None and nxt.start < op.end and nxt.end <= op.end
                and nxt.dur < op.dur):
            containers.append(op)
        else:
            leaves.append(op)
    return leaves, containers


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or a log dir holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            leaves, containers = split_containers(_spans(lines["XLA Ops"]))
            modules = (_spans(lines["XLA Modules"])
                       if "XLA Modules" in lines else [])
            devices.append(Device(plane.name, modules, leaves, containers))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(s for s in _spans(ln)
                            if s.name.startswith(HOST_PREFIX))
    devices.sort(key=lambda d: d.name)
    host.sort(key=lambda s: s.start)
    return Trace(devices, host)


# -- interval arithmetic ----------------------------------------------------

def union(spans) -> list[tuple[int, int]]:
    """Merged (start, end) intervals covered by ``spans``."""
    out: list[list[int]] = []
    for s, e in sorted((x.start, x.end) if isinstance(x, Span) else x
                       for x in spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of [lo, hi] given merged busy intervals."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


# -- classification ---------------------------------------------------------

def op_name(span: Span) -> str:
    """The HLO instruction name of an op event (``fused_iterate_pallas.5``)."""
    return span.name.split(" = ", 1)[0].lstrip("%")


def module_name(span: Span) -> str:
    return _MODULE_HASH.sub("", span.name)


def is_kernel(span: Span) -> bool:
    return KERNEL_MARK in span.name


def is_collective(span: Span) -> bool:
    return _COLLECTIVE.search(span.name) is not None


def within(spans, lo: int, hi: int) -> list[Span]:
    return [s for s in spans if s.start >= lo and s.end <= hi]


def program_windows(dev: Device, lo: int, hi: int) -> list[Span]:
    """Launches of the iterate program: the module with most device time."""
    mods = within(dev.modules, lo, hi)
    if not mods:
        return []
    time: dict[str, int] = {}
    for m in mods:
        time[module_name(m)] = time.get(module_name(m), 0) + m.dur
    main = max(time, key=time.get)
    return [m for m in mods if module_name(m) == main]


def program_ops(dev: Device, lo: int, hi: int) -> list[Span]:
    """Leaf ops that ran inside launches of the iterate program."""
    wins = program_windows(dev, lo, hi)
    out, i = [], 0
    ops = sorted(within(dev.ops, lo, hi), key=lambda s: s.start)
    for w in sorted(wins, key=lambda s: s.start):
        while i < len(ops) and ops[i].start < w.start:
            i += 1
        while i < len(ops) and ops[i].start < w.end:
            if ops[i].end <= w.end:
                out.append(ops[i])
            i += 1
    return out


@dataclasses.dataclass
class DeviceSummary:
    """One device's reading over a window, in nanoseconds."""
    name: str
    window: int
    busy: int            # union of leaf op intervals
    kernel: int          # summed kernel op durations (iterate program)
    kernel_launches: int
    glue: int            # other leaf ops of the iterate program
    collective: int      # summed collective op durations
    collective_exposed: int  # collective time with no other op running
    has_program: bool


def summarize(dev: Device, lo: int, hi: int) -> DeviceSummary:
    ops = within(dev.ops, lo, hi)
    busy = clip(union(ops), lo, hi)
    prog = program_ops(dev, lo, hi)
    kern = [s for s in prog if is_kernel(s)]
    glue = [s for s in prog if not is_kernel(s)]
    coll = [s for s in ops if is_collective(s)]
    other = union(s for s in ops if not is_collective(s))
    exposed = subtract(union(coll), other)
    return DeviceSummary(
        name=dev.name, window=hi - lo, busy=total(busy),
        kernel=sum(s.dur for s in kern), kernel_launches=len(kern),
        glue=sum(s.dur for s in glue),
        collective=sum(s.dur for s in coll),
        collective_exposed=total(exposed), has_program=bool(prog))


def host_spans(trace: Trace, name: str, lo: int, hi: int) -> list[Span]:
    return [s for s in trace.host
            if s.name == name and s.start >= lo and s.end <= hi]


def window_of(trace: Trace, name: str = "bench.window") -> tuple[int, int]:
    """The traced window: the benchmark's own window span."""
    spans = [s for s in trace.host if s.name == name]
    if not spans:
        raise ValueError(f"trace has no {name!r} host span")
    return spans[0].start, spans[-1].end


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10):
    """[[op name, seconds per device], ...] of the ops that took most time."""
    acc: dict[str, int] = {}
    for dev in trace.devices:
        for s in within(dev.ops, lo, hi):
            acc[op_name(s)] = acc.get(op_name(s), 0) + s.dur
    ndev = max(1, len(trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / ndev / 1e9] for k, v in ranked]


def idle_by_host(trace: Trace, lo: int, hi: int, n: int = 10):
    """[[host activity, idle seconds per device], ...]: each device gap is
    named by the innermost ``bench.*`` span covering its midpoint."""
    host = [h for h in trace.host if h.name != "bench.window"]
    starts = [h.start for h in host]
    acc: dict[str, list[int]] = {}
    for dev in trace.devices:
        for s, e in gaps(union(within(dev.ops, lo, hi)), lo, hi):
            mid = (s + e) // 2
            i = bisect.bisect_right(starts, mid)
            # bench spans nest a few deep: the innermost that covers the
            # midpoint is among the last few that start before it.
            inner = [h for h in host[max(0, i - 8):i] if mid < h.end]
            label = (min(inner, key=lambda h: h.dur).name if inner
                     else "outside bench spans")
            slot = acc.setdefault(label, [0, 0])
            slot[0] += e - s
            slot[1] += 1
    ndev = max(1, len(trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
    return [[f"{k} ({v[1]} gaps)", v[0] / ndev / 1e9] for k, v in ranked]
