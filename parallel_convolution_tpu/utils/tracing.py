"""Tracing / profiling hooks (SURVEY.md §5 aux subsystems).

The reference's only observability is hand-placed ``MPI_Wtime`` segments
printed at the end.  Here:

* :class:`PhaseTimer` — named wall-clock phases (load / compile / iterate /
  write) with a structured report, the upgrade over printf timings.  Device
  work is fenced with ``jax.block_until_ready`` so a phase means what it
  says under async dispatch.  Phases NEST (``queue`` around
  ``compile``/``device``/``copy`` is the serving layer's per-request
  breakdown); nested walls are recorded under slash-joined paths and a
  flat :meth:`PhaseTimer.to_row` export merges them into bench-row dicts.
  Each phase also writes a profiler event, ``pctpu.phase.<path>``
  (``obs.trace.annotate``), so the serving phases sit on a
  ``jax.profiler`` trace beside the device ops.
* :func:`device_trace` — context manager around ``jax.profiler.trace``;
  writes a TensorBoard/Perfetto trace of the XLA execution (the real
  per-op timeline the reference never had).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

import jax

from parallel_convolution_tpu.obs import trace as obs_trace


class PhaseTimer:
    """Accumulate named wall-clock phases.

    >>> t = PhaseTimer()
    >>> with t.phase("iterate"):
    ...     out = run()          # doctest: +SKIP
    >>> t.report()               # doctest: +SKIP

    Phases nest: entering ``phase("device")`` inside ``phase("serve")``
    accumulates under the path ``"serve/device"`` while ``"serve"`` keeps
    the enclosing wall — so a report's top-level walls stay additive and
    nested ones attribute where the time inside them went.

    Thread-safe (round 11): the nesting stack is **thread-local** — each
    thread nests against its own enclosing phases, never another
    thread's — and the accumulated walls/counts are lock-protected.  A
    timer shared between the batcher worker and HTTP handler threads
    therefore records correct per-thread paths instead of silently
    corrupting one shared stack (the pre-round-11 failure mode, pinned
    by ``tests/test_obs.py::test_phase_timer_thread_safety``).
    """

    def __init__(self) -> None:
        self.walls: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()

    def _stack(self) -> list[str]:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    @contextlib.contextmanager
    def phase(self, name: str, fence=None):
        """Time a phase; ``fence`` (a jax value/tree) is block_until_ready'd
        before the clock stops so async device work is charged here."""
        stack = self._stack()
        stack.append(name)
        path = "/".join(stack)
        event = obs_trace.annotate("pctpu.phase." + path).__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            try:
                if fence is not None:
                    jax.block_until_ready(fence)
            finally:
                event.__exit__(None, None, None)
                # Record + pop even when the body OR the fence raised:
                # a failing phase must not corrupt the nesting stack (the
                # fault/retry paths re-enter the same timer afterwards).
                dt = time.perf_counter() - t0
                stack.pop()
                with self._lock:
                    self.walls[path] = self.walls.get(path, 0.0) + dt
                    self.counts[path] = self.counts.get(path, 0) + 1

    def report(self) -> dict:
        with self._lock:
            walls = dict(self.walls)
            counts = dict(self.counts)
        # Total sums only TOP-LEVEL phases: a nested wall is already inside
        # its parent's, so summing every path would double-count it.
        total = sum(v for k, v in walls.items() if "/" not in k)
        return {
            "total_s": round(total, 4),
            "phases": {
                k: {"wall_s": round(v, 4), "calls": counts[k],
                    "share": round(v / total, 3) if total else 0.0}
                for k, v in sorted(walls.items(), key=lambda kv: -kv[1])
            },
        }

    def to_row(self, prefix: str = "phase_", scale: float = 1.0,
               digits: int = 6) -> dict:
        """Flat ``{prefix<path>_s: wall}`` dict for merging into bench rows.

        Nested paths flatten with underscores (``serve/device`` →
        ``phase_serve_device_s``).  ``scale`` converts units (1e3 = ms, with
        the key suffix left to the caller's prefix convention); the serving
        latency breakdown merges this straight into its per-request and
        loadgen rows.

        Flattening can collide: the nested path ``a/b`` and a top-level
        phase literally named ``a_b`` map to the same row key.  Colliding
        walls are SUMMED — a collision may blur attribution between two
        sources but can never silently drop one of them (pinned in
        tests/test_obs.py).
        """
        with self._lock:
            walls = dict(self.walls)
        out: dict[str, float] = {}
        for k, v in walls.items():
            key = f"{prefix}{k.replace('/', '_')}_s"
            out[key] = out.get(key, 0.0) + v * scale
        return {k: round(v, digits) for k, v in out.items()}

    def wall(self, name: str) -> float:
        """Accumulated seconds for one phase path (0.0 if never entered)."""
        with self._lock:
            return self.walls.get(name, 0.0)

    def dump(self, path) -> None:
        with open(path, "w") as f:  # diskio: exempt — exit-time report
            json.dump(self.report(), f, indent=2)


@contextlib.contextmanager
def device_trace(logdir: str):
    """Capture an XLA device trace viewable in TensorBoard / Perfetto."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
