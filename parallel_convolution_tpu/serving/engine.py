"""Warm-executable engine: compile once per key, serve many times.

A one-shot entry point (CLI ``run``, ``bench.py``) pays trace + compile +
mesh setup on every invocation; a service must pay them once per
*configuration* and amortize across the request stream — the serving
analogue of persistent MPI channels (PAPERS.md "Persistent and
Partitioned MPI for Stencil Communication": set the communication/compute
schedule up once, reuse it for every iteration).

Design points:

* **Key = full compile identity.**  :class:`EngineKey` carries everything
  that changes the compiled program: per-request image shape, filter,
  storage dtype, iteration count, fuse, boundary, quantize, requested
  backend — plus the engine's mesh grid.  Two requests with equal keys
  are guaranteed to share an executable (and therefore to be safely
  micro-batchable).
* **LRU eviction.**  The cache holds at most ``capacity`` keys; touching
  a key refreshes it.  Eviction drops the engine's reference (the
  underlying ``parallel.step._build_iterate`` lru_cache may briefly keep
  the jitted runner alive; that cache is bounded too).
* **Per-key single-flight.**  A cold key compiles exactly once no matter
  how many threads ask for it concurrently: one leader compiles, the
  rest wait on the in-flight event — a thundering herd of identical cold
  requests can never stampede the compiler.
* **Degradation per key (opt-in).**  With ``fallback=True`` the
  requested backend is resolved through the resilience ladder
  (``resilience.degrade``: probe once, walk pallas_rdma → pallas →
  shifted on classified-transient compile faults); the entry records the
  ``effective_backend`` that every response is stamped with.

Batched execution stacks B same-key images on the leading dim and folds
them into the plane axis — ``(B, C, H, W) → (B*C, H, W)`` — which is the
framework's established data-parallel tier (``ConvolutionModel.run_images``
concatenates planes the same way; SURVEY.md §2: DP "falls out free"
because every plane is independent in the stencil).  The fold is exactly
a vmap of the prepared per-image step over the stacked dim, realized on
the axis the compiled runner already treats as batch — so batched bytes
are identical to sequential single-request bytes by construction, which
``tests/test_serving.py`` asserts per backend.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict

import numpy as np

from parallel_convolution_tpu.obs import (
    events as obs_events, metrics as obs_metrics, trace as obs_trace,
)
from parallel_convolution_tpu.ops.filters import get_filter
from parallel_convolution_tpu.utils.config import (
    BACKENDS, BOUNDARIES, SOLVERS, STORAGES,
)
from parallel_convolution_tpu.utils.tracing import PhaseTimer

__all__ = ["EngineKey", "WarmEngine", "bucket_extent", "bucket_key"]


@dataclasses.dataclass(frozen=True)
class EngineKey:
    """The compile identity of one servable configuration.

    ``shape`` is ONE request's planar image shape (C, H, W); the batch
    dim is not part of the key — executables per batch size live inside
    the key's cache entry.  ``grid`` pins the mesh the executable was
    built for, so an engine restarted on different hardware can never
    alias a stale key.
    """

    shape: tuple[int, int, int]      # (C, H, W) of one request
    filter_name: str = "blur3"
    storage: str = "f32"
    iters: int = 1
    fuse: int = 1
    boundary: str = "zero"
    quantize: bool = True
    backend: str = "shifted"         # requested; the entry records effective
    grid: tuple[int, int] = (1, 1)   # mesh grid (rows, cols)
    tile: tuple[int, int] | None = None  # Pallas kernel tile (None=default)
    overlap: bool = False            # RESOLVED interior-first overlapped
    #                                  halo pipeline knob (resolve_key
    #                                  settles None/auto before keying, so
    #                                  equal executables share one key)
    col_mode: str = "packed"         # RESOLVED column-slab transport
    #                                  (packed | strided) — same pre-keying
    #                                  rule as overlap/backend: auto and
    #                                  explicit requests that compile the
    #                                  same program share one warm
    #                                  executable
    solver: str = "jacobi"           # convergence strategy (SOLVERS):
    #                                  "multigrid" keys the V-cycle's
    #                                  compiled level programs (converge
    #                                  jobs only — the batch path is
    #                                  solver-less and rejects it)
    mg_levels: int | None = None     # multigrid level-count cap (part of
    #                                  the compile identity: it changes
    #                                  the level schedule)
    rank: int = 2                    # stencil rank (utils.config.RANKS):
    #                                  rank=3 keys a VOLUME config —
    #                                  ``shape`` is then (D, H, W) of one
    #                                  two-field volume, ``filter_name``
    #                                  a registered rank-3 form, and the
    #                                  executables come from
    #                                  volumes.driver instead of
    #                                  parallel.step

    def validate(self) -> None:
        """Terminal (ValueError) on any out-of-registry field — the typed
        ``Rejected("invalid")`` the service returns comes from here.

        ``backend="auto"`` never reaches here: :meth:`WarmEngine.key_for`
        resolves it to a concrete tier first, so two requests that tune
        to the same program share one key (and one executable)."""
        from parallel_convolution_tpu.utils.config import RANKS

        if self.rank not in RANKS:
            raise ValueError(f"rank must be one of {RANKS}, "
                             f"got {self.rank}")
        if self.rank == 3:
            self._validate_volume()
            return
        get_filter(self.filter_name)  # raises on unknown names
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r} (auto is "
                             "resolved in key_for, never stored in a key)")
        if self.storage not in STORAGES:
            raise ValueError(f"unknown storage {self.storage!r}")
        if self.boundary not in BOUNDARIES:
            raise ValueError(f"unknown boundary {self.boundary!r}")
        if self.storage == "u8" and not self.quantize:
            raise ValueError("storage='u8' requires quantize=True")
        if len(self.shape) != 3 or min(self.shape) < 1:
            raise ValueError(f"bad planar shape {self.shape}")
        if self.iters < 1 or self.fuse < 1:
            raise ValueError("iters and fuse must be >= 1")
        if self.tile is not None and (
                len(self.tile) != 2 or min(self.tile) < 1):
            raise ValueError(f"tile must be two positive ints, "
                             f"got {self.tile}")
        from parallel_convolution_tpu.parallel import channels

        if self.col_mode not in channels.COL_MODES:
            raise ValueError(
                f"unknown col_mode {self.col_mode!r} (auto is resolved "
                "in key_for, never stored in a key)")
        if self.solver not in SOLVERS:
            raise ValueError(f"unknown solver {self.solver!r}")
        if self.mg_levels is not None and int(self.mg_levels) < 1:
            raise ValueError(f"mg_levels must be >= 1, got {self.mg_levels}")
        if self.solver == "multigrid":
            # V-cycle residual/correction fields are signed floats — a
            # u8 store-back would clamp the error equation to garbage.
            if self.quantize:
                raise ValueError("solver='multigrid' requires "
                                 "quantize=False")
            if self.storage != "f32":
                raise ValueError("solver='multigrid' requires "
                                 "storage='f32'")

    def _validate_volume(self) -> None:
        """Rank-3 key constraints.  ``shape`` is (D, H, W) of one
        two-field volume; ``filter_name`` must resolve in the rank-3
        registry (raises with the registered names on a miss).  Volumes
        are float fields end to end, serve on the registry path (no
        backend ladder, no Pallas tier, no overlap pipeline), and
        converge through the chunked-jacobi driver."""
        from parallel_convolution_tpu.parallel import (
            kernels as kernel_forms,
        )

        if self.boundary not in BOUNDARIES:
            raise ValueError(f"unknown boundary {self.boundary!r}")
        kernel_forms.resolve(3, self.filter_name, self.boundary)
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if len(self.shape) != 3 or min(self.shape) < 1:
            raise ValueError(f"bad volume shape {self.shape} "
                             "(want (D, H, W))")
        if self.iters < 1 or self.fuse < 1:
            raise ValueError("iters and fuse must be >= 1")
        if self.quantize or self.storage != "f32":
            raise ValueError("rank-3 volumes are float fields: "
                             "storage='f32' and quantize=False required")
        if self.solver != "jacobi":
            raise ValueError("rank-3 convergence is the chunked-jacobi "
                             f"driver; solver={self.solver!r} is rank-2 "
                             "only")
        if self.tile is not None or self.overlap:
            raise ValueError("rank-3 keys have no kernel tile or "
                             "overlapped-halo form")
        if self.col_mode != "packed":
            raise ValueError("rank-3 keys use the canonical 'packed' "
                             "column transport label")


# Shape-bucket extent ladder for lane co-batching: dense at thumbnail
# sizes (where request mixes cluster), sparse above, capped pad waste
# (~1.33x worst-case per dim between rungs).
_BUCKET_LADDER = (8, 16, 32, 48, 64, 96, 128, 192, 256, 384, 512, 768,
                  1024, 1280, 1536, 1920, 2048, 2560, 3072, 4096)


def bucket_extent(v: int) -> int:
    """Round one spatial extent UP to its lane bucket rung.

    Rounding up (never down) keeps every geometry-derived validity
    check (block >= radius*fuse, halo fits) at least as satisfied for
    the bucket as for the original extent.  Above the ladder, round up
    to the next multiple of the top rung spacing."""
    v = int(v)
    for rung in _BUCKET_LADDER:
        if v <= rung:
            return rung
    step = 1024
    return ((v + step - 1) // step) * step


def bucket_key(key):
    """The LANE a key batches under — ``key`` itself when pad-to-bucket
    co-batching cannot be proven byte-identical.

    Zero-padding the (H, W) margin is results-invariant ONLY for one
    Jacobi iteration under zero boundaries: the padded region is zero,
    one pointwise stencil application over a zero-margin image writes
    the same interior bytes as the unpadded program (per-pixel
    shifted-add summation order does not depend on extent), and the
    crop discards the rest.  Reflect/edge boundaries read the margin,
    and iters > 1 propagates it inward — those keys get a degenerate
    exact-key lane (same behavior as before this round).
    """
    if not isinstance(key, EngineKey):
        return key
    if key.rank != 2:
        # A rank-3 volume's zero-pad margin changes the stencil's D-face
        # geometry reading, and co-batching across (D, H, W) shapes was
        # never proven byte-identical — volumes get exact-key lanes.
        return key
    if key.iters != 1 or key.boundary != "zero" or key.solver != "jacobi":
        return key
    c, h, w = key.shape
    bh, bw = bucket_extent(h), bucket_extent(w)
    if (bh, bw) == (h, w):
        return key
    return dataclasses.replace(key, shape=(c, bh, bw))


class _Entry:
    """One warm key: resolved backend + compiled runners per batch size."""

    __slots__ = ("key", "launch", "effective_backend", "fns", "lock",
                 "plan_source", "predicted_gpx", "plan_key",
                 "effective_overlap", "effective_col_mode", "splits",
                 "compile_ref", "converge_fns", "mg_levels", "compiles")

    def __init__(self, key: EngineKey, launch=None,
                 plan_source: str = "explicit",
                 predicted_gpx: float | None = None,
                 plan_key: str = ""):
        self.key = key
        # The launch the executables are ACTUALLY built from
        # (step.resolve_launch: the degrade walk and its re-clamps, and
        # the block every prepared batch pads to); None for a rank-3
        # volume key — one registry program, no lower tier.
        self.launch = launch
        self.effective_backend = (launch.backend if launch is not None
                                  else key.backend)
        self.effective_overlap = (launch.overlap if launch is not None
                                  else False)
        self.effective_col_mode = (launch.col_mode if launch is not None
                                   else "packed")
        self.plan_source = plan_source       # explicit|measured|
        #                                      interpolated|predicted
        self.predicted_gpx = predicted_gpx   # cost-model Gpx/s/chip
        self.plan_key = plan_key             # tuning canonical key: the
        #                                      drift series' label
        self.compile_ref: dict | None = None  # the single-flight leader's
        #                                      compile_build span ref —
        #                                      waiters (and reports) link
        #                                      to WHO paid for the compile
        self.mg_levels: int | None = None  # multigrid keys: the level
        #                                    count the planner ACTUALLY
        #                                    scheduled (resolved at the
        #                                    first converge stream; the
        #                                    post-resolution stamp rows
        #                                    carry — never the cap)
        self.compiles = 0   # executables built FOR THIS KEY (batch sizes
        #                     + converge chunks) — the per-shard compile
        #                     ledger the warm-placement gate reads: a
        #                     pre-warmed joining replica's shard keys
        #                     must hold this flat through the remapped
        #                     traffic that follows ring join.
        self.fns: dict[int, object] = {}   # batch size -> jitted runner
        self.converge_fns: dict[int, object] = {}  # chunk length n ->
        #                                    jitted convergence chunk
        #                                    (run_converge's progressive
        #                                    executables; n varies only on
        #                                    the final short chunk)
        self.splits: dict[int, dict] = {}  # batch size -> exchange split
        #                                    (pure model math, cached off
        #                                    the per-request hot path;
        #                                    batch-dependent only via the
        #                                    RDMA tiled-kernel switch)
        self.lock = threading.Lock()       # per-batch-size build flight


class _InFlight:
    """A cold key's compilation in progress: leader fills, waiters wait."""

    __slots__ = ("event", "entry", "error", "span_ref")

    def __init__(self):
        self.event = threading.Event()
        self.entry: _Entry | None = None
        self.error: BaseException | None = None
        self.span_ref: dict | None = None  # leader's compile_build span


class WarmEngine:
    """Warm-executable cache over ``parallel.step`` for a fixed mesh."""

    def __init__(self, mesh=None, capacity: int = 16, fallback: bool = False,
                 plans=None):
        from parallel_convolution_tpu.parallel.mesh import make_grid_mesh

        self.mesh = mesh if mesh is not None else make_grid_mesh()
        self.capacity = max(1, int(capacity))
        self.fallback = fallback
        # The plan cache backend="auto" keys resolve through: a
        # tuning.PlanCache, a path to a plan file, or None (ambient
        # PCTPU_PLAN_FILE, else pure cost model).
        if isinstance(plans, str):
            from parallel_convolution_tpu.tuning import PlanCache

            plans = PlanCache.load(plans)
        self.plans = plans
        self._lock = threading.Lock()
        self._entries: OrderedDict[EngineKey, _Entry] = OrderedDict()
        self._inflight: dict[EngineKey, _InFlight] = {}
        # Resolution provenance per auto-resolved key (stamped into the
        # entry at build time; explicit keys default to 'explicit').
        self._plan_sources: dict[EngineKey, str] = {}
        # The legacy stats dict, now a view over the obs registry: every
        # write mirrors into pctpu_engine_stats{key=...} (obs.metrics).
        self.stats = obs_metrics.MirroredStats(obs_metrics.gauge(
            "pctpu_engine_stats", "warm-engine cache/execution counters",
            ("key",)), initial={
            "hits": 0, "misses": 0, "compiles": 0, "evictions": 0,
            "single_flight_waits": 0, "batches": 0, "images": 0,
            "reshapes": 0,
        })

    def grid(self) -> tuple[int, int]:
        from parallel_convolution_tpu.parallel.mesh import grid_shape

        return grid_shape(self.mesh)

    # -- elastic recovery ---------------------------------------------------
    def reshape(self, mesh) -> dict:
        """Re-bind the engine onto a different mesh MID-PROCESS.

        The serve-through-shrink leg: drop every warm entry (they were
        compiled for the old grid), swap the mesh, and re-warm the
        previously-resident keys on the new grid — so the first request
        after a shrink hits a warm executable, not a cold compile.  A key
        whose image cannot fit the new grid (block < radius*fuse) is
        SKIPPED with a warning, never fatal: serve-through-shrink must
        not die because one tiny config has no home on the smaller mesh.

        The caller must quiesce execution first — the service drains its
        batcher before calling this (``ConvolutionService.reshape``), so
        no in-flight ``run_batch`` can straddle the swap; a stale-grid
        key reaching :meth:`run_batch` afterwards raises (terminal), it
        can never silently run on the wrong decomposition.
        """
        import warnings

        from parallel_convolution_tpu.parallel.mesh import grid_shape

        new_grid = grid_shape(mesh)
        with self._lock:
            old_grid = self.grid()
            old_keys = list(self._entries)
            self._entries.clear()
            self._plan_sources.clear()
            self._inflight.clear()
            self.mesh = mesh
            self.stats["reshapes"] += 1
        rewarmed, skipped = [], []
        for k in old_keys:
            nk = dataclasses.replace(k, grid=new_grid)
            try:
                self.entry(nk)
                rewarmed.append(nk)
            except Exception as e:  # noqa: BLE001 — per-key, never fatal
                skipped.append((nk, repr(e)[:200]))
                warnings.warn(
                    f"reshape: key {k.filter_name}/{k.shape} has no home "
                    f"on grid {new_grid}: {e}", stacklevel=2)
        info = {
            "old_grid": old_grid, "grid": new_grid,
            "rewarmed": len(rewarmed), "skipped": len(skipped),
        }
        if obs_metrics.enabled():
            obs_events.emit(
                "reshape", old_grid=f"{old_grid[0]}x{old_grid[1]}",
                grid=f"{new_grid[0]}x{new_grid[1]}",
                rewarmed=info["rewarmed"], skipped=info["skipped"])
        return info

    # -- key construction ---------------------------------------------------
    def resolve_key(self, shape, **kw) -> tuple[EngineKey, str]:
        """``(EngineKey, plan_source)`` for this engine's mesh; clamps
        fuse the way ``_build_iterate`` will, so equal executables get
        equal keys.

        ``backend="auto"`` (with ``fuse=None``/``tile=None`` meaning
        'tune it') resolves through the tuning subsystem HERE — against
        this engine's plan cache — so an auto request and an explicit
        request for the tuned config produce the SAME key and share one
        warm executable.  ``plan_source`` is THIS call's provenance
        ('explicit' for named configs): responses must stamp per-request
        provenance, because an auto and an explicit request can share a
        key (and an entry) while having different origins.
        """
        from parallel_convolution_tpu.parallel.mesh import grid_shape

        from parallel_convolution_tpu.parallel import step as step_lib

        kw = dict(kw)
        if int(kw.get("rank", 2)) == 3:
            # Volumes have no tuning space (one registry path, no tile,
            # no overlap, no column A/B): "auto" normalizes to the
            # canonical shifted label and the knobs to their clamped
            # values, so every spelling of a volume config shares one
            # key.  Everything else is validated by the key itself.
            if kw.get("backend") in (None, "auto"):
                kw["backend"] = "shifted"
            kw["overlap"] = bool(kw.get("overlap") or False)
            kw["col_mode"] = ("packed" if kw.get("col_mode") in
                              (None, "auto") else kw["col_mode"])
            kw["fuse"] = max(1, int(kw.get("fuse") or 1))
            key = EngineKey(shape=tuple(int(s) for s in shape),
                            grid=grid_shape(self.mesh), **kw)
            if key.fuse > max(1, key.iters):
                key = dataclasses.replace(key, fuse=max(1, key.iters))
            return key, "explicit"
        if kw.get("fuse") is None and "fuse" in kw \
                and kw.get("backend") != "auto":
            # Same contract as RunConfig/ConvolutionModel: fuse=None
            # means 'tune it', which needs backend='auto' — silently
            # running an explicit backend at fuse=1 would accept here
            # what every other entry point rejects as invalid.
            raise ValueError(
                "fuse=None means 'tune it' and needs backend='auto'")
        # Key from the resolved launch (backend="auto" through this
        # engine's plan cache; overlap and column transport settled) —
        # two requests that compile the same program share one key, and
        # the key states the compiled form.
        launch = step_lib.resolve_launch(
            self.mesh, get_filter(kw.get("filter_name", "blur3")),
            tuple(int(v) for v in shape),
            backend=kw.get("backend", "shifted"),
            storage=kw.get("storage", "f32"),
            quantize=bool(kw.get("quantize", True)),
            boundary=kw.get("boundary", "zero"), fuse=kw.get("fuse", 1),
            tile=kw.get("tile"), overlap=kw.get("overlap"),
            col_mode=kw.get("col_mode"), plans=self.plans)
        kw.update(backend=launch.backend, fuse=launch.fuse,
                  tile=launch.tile, overlap=launch.overlap,
                  col_mode=launch.col_mode)
        plan_source = launch.plan_source
        key = EngineKey(shape=tuple(int(s) for s in shape),
                        grid=grid_shape(self.mesh), **kw)
        if key.fuse > max(1, key.iters):
            key = dataclasses.replace(key, fuse=max(1, key.iters))
        if plan_source != "explicit":
            with self._lock:
                self._plan_sources[key] = plan_source
                # Bounded independently of _entries: keys stamped here can
                # be rejected before any entry exists (queue_full, block
                # validation), so LRU eviction alone would never trim
                # them — adversarially varied auto traffic must not grow
                # this side table forever.  FIFO is fine: a dropped note
                # is re-stamped by the next resolve_key for that key.
                limit = max(64, 4 * self.capacity)
                while len(self._plan_sources) > limit:
                    self._plan_sources.pop(next(iter(self._plan_sources)))
        return key, plan_source

    def key_for(self, shape, **kw) -> EngineKey:
        """:meth:`resolve_key` without the provenance (compat surface)."""
        return self.resolve_key(shape, **kw)[0]

    # -- entry acquisition (LRU + single-flight) ----------------------------
    def entry(self, key: EngineKey) -> _Entry:
        """The warm entry for ``key``; compiles (single-flight) when cold."""
        while True:
            with self._lock:
                e = self._entries.get(key)
                if e is not None:
                    self._entries.move_to_end(key)
                    self.stats["hits"] += 1
                    return e
                fl = self._inflight.get(key)
                if fl is None:
                    fl = _InFlight()
                    self._inflight[key] = fl
                    self.stats["misses"] += 1
                    leader = True
                else:
                    self.stats["single_flight_waits"] += 1
                    leader = False
            if not leader:
                fl.event.wait()
                if fl.error is not None:
                    raise fl.error
                # Single-flight attribution (obs.trace): this thread did
                # not compile — link the LEADER's compile_build span onto
                # our enclosing span (run_batch's compile phase), so the
                # trace report can tell who paid and who drafted.
                if fl.span_ref is not None:
                    obs_trace.add_link(fl.span_ref, kind="single_flight")
                # The leader landed the entry; loop to take the hit path
                # (or recompile if an eviction already dropped it).
                with self._lock:
                    e = self._entries.get(key)
                    if e is not None:
                        self._entries.move_to_end(key)
                        return e
                continue
            try:
                with obs_trace.span(
                        "compile_build", backend=key.backend,
                        filter=key.filter_name, fuse=key.fuse,
                        shape=list(key.shape)) as bsp:
                    entry = self._build_entry(key)
                    entry.compile_ref = fl.span_ref = bsp.ref
            except BaseException as err:
                fl.error = err
                with self._lock:
                    self._inflight.pop(key, None)
                fl.event.set()
                raise
            with self._lock:
                self._entries[key] = entry
                self._entries.move_to_end(key)
                while len(self._entries) > self.capacity:
                    old_key, _ = self._entries.popitem(last=False)
                    # Drop the provenance note too (re-resolved on the
                    # next auto key_for); keeps the side table bounded.
                    self._plan_sources.pop(old_key, None)
                    self.stats["evictions"] += 1
                self._inflight.pop(key, None)
            fl.event.set()
            return entry

    def _build_entry(self, key: EngineKey) -> _Entry:
        """Resolve the backend (degradation ladder) and compile batch=1.

        Runs OUTSIDE the engine lock (compiles take seconds on real
        chips); the single-flight record keeps concurrent cold callers
        from duplicating the work.
        """
        key.validate()
        if key.rank == 3:
            # No degrade walk and no tuning Workload: the volume path is
            # one registry program per (form, boundary) — there is no
            # lower tier to fall to, and a fault in it is terminal by
            # design.  The cost-model stamp comes from the rank-3
            # roofline so predicted-vs-measured visibility survives.
            from parallel_convolution_tpu.tuning import costmodel
            from parallel_convolution_tpu.utils.config import (
                VOLUME_FIELDS, VOLUME_RADII,
            )

            dev0 = self.mesh.devices.flat[0]
            hw = costmodel.hardware_for(
                dev0.platform, getattr(dev0, "device_kind", "") or "")
            D = key.shape[0]
            predicted = costmodel.predict_gpx_per_chip(
                costmodel.predict_volume_seconds_per_cell_iter(
                    key.grid, self._block_hw(key), D,
                    VOLUME_RADII[key.filter_name], key.fuse,
                    key.filter_name, hw, fields=VOLUME_FIELDS))
            plan_key = (f"vol|{key.filter_name}|{key.shape[0]}x"
                        f"{key.shape[1]}x{key.shape[2]}|{key.boundary}"
                        f"|grid={key.grid[0]}x{key.grid[1]}")
            entry = _Entry(key, plan_source="explicit",
                           predicted_gpx=round(predicted, 3),
                           plan_key=plan_key)
            self._compile_batch(entry, 1)
            return entry
        from parallel_convolution_tpu.parallel import step as step_lib

        # The key's knobs are already resolved, so this call adds only
        # the degrade walk (when the engine has one) and its re-clamps.
        launch = step_lib.resolve_launch(
            self.mesh, get_filter(key.filter_name), key.shape,
            backend=key.backend, storage=key.storage,
            quantize=key.quantize, boundary=key.boundary, fuse=key.fuse,
            tile=key.tile, overlap=key.overlap, col_mode=key.col_mode,
            fallback=self.fallback)
        # Cost-model figure for the config actually compiled: every
        # response carries predicted-vs-measured visibility, so a silent
        # mistune (or a degraded tier) shows in per-request artifacts.
        from parallel_convolution_tpu.tuning import costmodel, search
        from parallel_convolution_tpu.tuning.plans import Workload

        w = Workload.from_mesh(self.mesh, get_filter(key.filter_name),
                               key.shape, storage=key.storage,
                               quantize=key.quantize,
                               boundary=key.boundary)
        predicted = costmodel.predict_gpx_per_chip(search.predict(
            w, search.Candidate(launch.backend, launch.fuse, launch.tile,
                                launch.overlap, launch.col_mode)))
        with self._lock:
            source = self._plan_sources.get(key, "explicit")
        launch = dataclasses.replace(launch, plan_source=source)
        entry = _Entry(key, launch, plan_source=source,
                       predicted_gpx=round(predicted, 3), plan_key=w.key())
        self._compile_batch(entry, 1)
        return entry

    def _entry_block(self, entry: _Entry) -> tuple[int, int]:
        """The per-device block an entry's executables run on."""
        if entry.launch is not None:
            return entry.launch.block_hw
        return self._block_hw(entry.key)

    def _block_hw(self, key: EngineKey) -> tuple[int, int]:
        from parallel_convolution_tpu.parallel.mesh import padded_extent

        (_, H, W), (R, C) = key.shape, key.grid
        return (padded_extent(H, R) // R, padded_extent(W, C) // C)

    def _compile_batch(self, entry: _Entry, batch: int):
        """The jitted runner for ``batch`` stacked requests of this key."""
        with entry.lock:
            fn = entry.fns.get(batch)
            if fn is not None:
                return fn
            if entry.key.rank == 3:
                return self._compile_volume_batch(entry, batch)
            from parallel_convolution_tpu.parallel import step as step_lib

            key = entry.key
            C, H, W = key.shape
            filt = get_filter(key.filter_name)
            # Folded leading dim: batch × channels independent planes.
            probe = np.zeros((batch * C, H, W), np.float32)
            xs, valid_hw, _ = step_lib._prepare(probe, self.mesh,
                                                entry.launch)
            fn = step_lib._iterate_runner(self.mesh, filt, key.iters,
                                          key.quantize, valid_hw,
                                          entry.launch)
            # Trace + XLA-compile NOW (jit compiles on first call): warm
            # means the request path never sees compilation.
            import jax

            jax.block_until_ready(fn(xs))
            entry.fns[batch] = fn
            entry.compiles += 1
            with self._lock:
                self.stats["compiles"] += 1
            return fn

    def _compile_volume_batch(self, entry: _Entry, batch: int):
        """The rank-3 twin of the batch compile: ``batch`` volumes fold
        their field pairs onto the leading axis — (B, F, D, H, W) →
        (B*F, D, H, W), the volume driver's interleaved-field contract —
        and the runner comes from ``volumes.driver``.  Caller holds
        ``entry.lock``."""
        import jax

        from parallel_convolution_tpu.utils.config import VOLUME_FIELDS
        from parallel_convolution_tpu.volumes import driver

        key = entry.key
        D, H, W = key.shape
        F = batch * VOLUME_FIELDS
        probe = np.zeros((F, D, H, W), np.float32)
        xs, valid_hw = driver.prepare_volume(probe, self.mesh,
                                             key.boundary)
        _, block_hw, _ = driver._geometry((F, D, H, W), self.mesh,
                                          key.boundary)
        fn = driver._build_volume_iterate(
            self.mesh, key.filter_name, key.iters, D, valid_hw,
            block_hw, key.fuse, key.boundary)
        jax.block_until_ready(fn(xs))
        entry.fns[batch] = fn
        entry.compiles += 1
        with self._lock:
            self.stats["compiles"] += 1
        return fn

    # -- warmup -------------------------------------------------------------
    def warmup(self, keys) -> list[str]:
        """Pre-compile declared configs (batch size 1); returns the
        effective backend per key, in order.

        No plan-file parameter ON PURPOSE: ``keys`` are already-resolved
        :class:`EngineKey` values, so arming the plan cache here would be
        too late to affect them (the trap is real: an auto key built
        before the plans load resolves against the cost model).  Arm
        ``self.plans`` (constructor ``plans=``, or
        ``ConvolutionService.warmup(plan_file=...)`` which loads BEFORE
        building keys) and then call this.
        """
        return [self.entry(k).effective_backend for k in keys]

    # -- execution ----------------------------------------------------------
    def run_batch(self, key: EngineKey, images: np.ndarray,
                  timer: PhaseTimer | None = None):
        """Run ``images`` (B, C, H, W) f32 through the warm executable.

        Returns ``(out, info)``: ``out`` is (B, C, H, W) float32 with the
        valid extent restored, ``info`` carries ``effective_backend`` and
        the compile/copy_in/device/copy_out phase walls (seconds) from
        ``timer`` (a fresh :class:`PhaseTimer` when not supplied — the
        serving latency breakdown reuses its ``to_row`` export).
        """
        import jax
        import jax.numpy as jnp

        from parallel_convolution_tpu.parallel import step as step_lib

        t = timer or PhaseTimer()
        if key.rank == 3:
            return self._run_volume_batch(key, images, t)
        B, C, H, W = images.shape
        if (C, H, W) != key.shape:
            raise ValueError(
                f"batch shape {(C, H, W)} does not match key {key.shape}")
        if key.grid != self.grid():
            # A key compiled for a pre-reshape grid must never execute on
            # the new decomposition (the service re-keys requests after
            # its drain, so this only fires on a caller bug).
            raise ValueError(
                f"stale key grid {key.grid}: engine mesh is now "
                f"{self.grid()} (resharded mid-process)")
        with t.phase("compile"):
            # The trace's compile span covers acquisition (warm hit or
            # cold build): the leader's compile_build nests inside it, a
            # single-flight waiter LINKS the leader's build span instead
            # (obs.trace — who paid vs who drafted).
            with obs_trace.span("compile", backend=key.backend,
                                batch=B) as csp:
                entry = self.entry(key)
                fn = entry.fns.get(B) or self._compile_batch(entry, B)
                csp.set(effective_backend=entry.effective_backend)
        filt = get_filter(key.filter_name)
        with t.phase("copy_in"):
            with obs_trace.span("copy_in", batch=B):
                folded = np.ascontiguousarray(
                    images.reshape(B * C, H, W).astype(np.float32))
                xs, valid_hw, _ = step_lib._prepare(folded, self.mesh,
                                                    entry.launch)
                jax.block_until_ready(xs)
        # The timer is shared across retry ATTEMPTS (the service re-invokes
        # run_batch with it), so telemetry must charge only THIS call's
        # device delta — a retried batch's drift/exchange series would
        # otherwise include the failed attempt's wall.
        dev_before = t.wall("device")
        with t.phase("device"):
            with obs_trace.span("device", batch=B,
                                backend=entry.effective_backend) as dsp:
                out = fn(xs)
                jax.block_until_ready(out)
        dev_s = t.wall("device") - dev_before
        with t.phase("copy_out"):
            with obs_trace.span("copy_out", batch=B):
                out = np.asarray(
                    out[:, : valid_hw[0], : valid_hw[1]].astype(jnp.float32))
                out = out.reshape(B, C, H, W)
        with self._lock:
            self.stats["batches"] += 1
            self.stats["images"] += B
        if obs_metrics.enabled():
            # Attach the (already closed) device span's context so the
            # model-attributed exchange/compute spans record_step emits
            # land as ITS children — the span tree's leaf level.
            with obs_trace.attach(dsp.context):
                self._record_batch_obs(entry, B, filt, dev_s)
        # Overlap-adjusted exchange attribution for the response (pure
        # model arithmetic — always on, obs or not): hidden vs exposed
        # exchange is how the overlapped-halo lever is judged per
        # request.  Cached per (entry, batch) — it is a pure function of
        # them (batch-dependent only via the RDMA tiled switch), and a
        # benign last-writer-wins race writes identical dicts.
        split = entry.splits.get(B)
        if split is None:
            from parallel_convolution_tpu.obs import attribution

            dev0 = self.mesh.devices.flat[0]
            split = attribution.predicted_exchange_split(
                key.grid, self._entry_block(entry), filt.radius,
                max(1, min(key.fuse, key.iters)),
                backend=entry.effective_backend, storage=key.storage,
                shape=(B * C, H, W), tile=key.tile, quantize=key.quantize,
                separable=entry.effective_backend in ("separable",
                                                      "pallas_sep"),
                platform=dev0.platform,
                device_kind=getattr(dev0, "device_kind", "") or "",
                overlap=entry.effective_overlap,
                col_mode=entry.effective_col_mode)
            entry.splits[B] = split
        info = {
            "effective_backend": entry.effective_backend,
            "effective_grid": f"{key.grid[0]}x{key.grid[1]}",
            "plan_source": entry.plan_source,
            "plan_key": entry.plan_key,
            "predicted_gpx_per_chip": entry.predicted_gpx,
            "batch_size": B,
            "overlap": entry.effective_overlap,
            "col_mode": entry.effective_col_mode,
            "exchange_fraction": round(split["exchange_fraction"], 4),
            "exchange_hidden_fraction": round(
                split["exchange_hidden_fraction"], 4),
            "phases": {name: t.wall(name)
                       for name in ("compile", "copy_in", "device",
                                    "copy_out")},
        }
        return out, info

    def _run_volume_batch(self, key: EngineKey, volumes: np.ndarray,
                          t: PhaseTimer):
        """The rank-3 arm of :meth:`run_batch`: ``volumes`` is
        (B, 2, D, H, W) float32, ``key.shape`` its (D, H, W).  Returns
        ``(out, info)`` with ``out`` the same shape float32 (no u8
        quantization — volumes are float fields end to end) and the
        same ``info`` stamps as rank 2; the exchange attribution comes
        from the rank-3 face-bytes model
        (``obs.attribution.volume_face_bytes_per_round``)."""
        import jax

        from parallel_convolution_tpu.utils.config import (
            VOLUME_FIELDS, VOLUME_RADII,
        )
        from parallel_convolution_tpu.volumes import driver

        if volumes.ndim != 5 or volumes.shape[1] != VOLUME_FIELDS:
            raise ValueError(
                f"volume batch must be (B, {VOLUME_FIELDS}, D, H, W), "
                f"got {volumes.shape}")
        B = volumes.shape[0]
        if tuple(volumes.shape[2:]) != key.shape:
            raise ValueError(
                f"batch volume shape {tuple(volumes.shape[2:])} does "
                f"not match key {key.shape}")
        if key.grid != self.grid():
            raise ValueError(
                f"stale key grid {key.grid}: engine mesh is now "
                f"{self.grid()} (resharded mid-process)")
        D, H, W = key.shape
        with t.phase("compile"):
            with obs_trace.span("compile", backend=key.backend,
                                batch=B, rank=3):
                entry = self.entry(key)
                fn = entry.fns.get(B) or self._compile_batch(entry, B)
        with t.phase("copy_in"):
            with obs_trace.span("copy_in", batch=B):
                folded = np.ascontiguousarray(
                    volumes.reshape(B * VOLUME_FIELDS, D, H, W)
                    .astype(np.float32))
                xs, valid_hw = driver.prepare_volume(
                    folded, self.mesh, key.boundary)
                jax.block_until_ready(xs)
        with t.phase("device"):
            with obs_trace.span("device", batch=B,
                                backend=entry.effective_backend):
                out = fn(xs)
                jax.block_until_ready(out)
        with t.phase("copy_out"):
            with obs_trace.span("copy_out", batch=B):
                out = np.asarray(out)[:, :, : valid_hw[0], : valid_hw[1]]
                out = out.reshape(B, VOLUME_FIELDS, D, H, W)
        with self._lock:
            self.stats["batches"] += 1
            self.stats["images"] += B
        split = entry.splits.get(B)
        if split is None:
            # Model-attributed exchange share: the rank-3 roofline with
            # and without its collective term (a 1x1 grid has none).
            from parallel_convolution_tpu.tuning import costmodel

            dev0 = self.mesh.devices.flat[0]
            hw = costmodel.hardware_for(
                dev0.platform, getattr(dev0, "device_kind", "") or "")
            r = VOLUME_RADII[key.filter_name]
            args = (self._block_hw(key), D, r, key.fuse, key.filter_name,
                    hw)
            total = costmodel.predict_volume_seconds_per_cell_iter(
                key.grid, *args, fields=B * VOLUME_FIELDS)
            local = costmodel.predict_volume_seconds_per_cell_iter(
                (1, 1), *args, fields=B * VOLUME_FIELDS)
            from parallel_convolution_tpu.obs import attribution

            face = attribution.volume_face_bytes_per_round(
                key.grid, self._block_hw(key), D, r, key.fuse,
                fields=B * VOLUME_FIELDS, storage=key.storage,
                boundary=key.boundary)
            split = {
                "exchange_fraction": max(0.0, 1.0 - local / total),
                "exchange_hidden_fraction": 0.0,  # no overlapped form
                "face_bytes": face["total"],
            }
            entry.splits[B] = split
        info = {
            "effective_backend": entry.effective_backend,
            "effective_grid": f"{key.grid[0]}x{key.grid[1]}",
            "plan_source": entry.plan_source,
            "plan_key": entry.plan_key,
            "predicted_gpx_per_chip": entry.predicted_gpx,
            "batch_size": B,
            "overlap": False,
            "col_mode": "packed",
            "exchange_fraction": round(split["exchange_fraction"], 4),
            "exchange_hidden_fraction": 0.0,
            "phases": {name: t.wall(name)
                       for name in ("compile", "copy_in", "device",
                                    "copy_out")},
        }
        return out, info

    def _record_batch_obs(self, entry: _Entry, B: int, filt,
                          dev_s: float) -> None:
        """Per-batch telemetry: halo/exchange attribution for THIS call's
        device wall plus the predicted-vs-measured drift series per plan
        key — the recalibration input ROADMAP item 5a consumes."""
        from parallel_convolution_tpu.obs import attribution

        key = entry.key
        C, H, W = key.shape
        dev0 = self.mesh.devices.flat[0]
        attribution.record_step(
            backend=entry.effective_backend, grid=key.grid,
            block_hw=self._entry_block(entry), radius=filt.radius,
            fuse=max(1, min(key.fuse, key.iters)), iters=key.iters,
            channels=B * C, storage=key.storage, boundary=key.boundary,
            wall_s=dev_s, shape=(B * C, H, W), quantize=key.quantize,
            tile=key.tile, platform=dev0.platform,
            device_kind=getattr(dev0, "device_kind", "") or "",
            source="serving", overlap=entry.effective_overlap,
            col_mode=entry.effective_col_mode)
        if dev_s > 0:
            attribution.record_drift(
                entry.plan_key, entry.effective_backend,
                entry.predicted_gpx,
                B * C * H * W * key.iters / dev_s / self.mesh.size / 1e9)

    # -- progressive convergence --------------------------------------------
    def _converge_fn(self, entry: _Entry, n: int):
        """The warm convergence-chunk executable for ``n`` iterations of
        this entry's config (compiled under the entry lock, cached)."""
        fn = entry.converge_fns.get(n)
        if fn is not None:
            return fn
        with entry.lock:
            fn = entry.converge_fns.get(n)
            if fn is not None:
                return fn
            if entry.key.rank == 3:
                import jax

                from parallel_convolution_tpu.utils.config import (
                    VOLUME_FIELDS,
                )
                from parallel_convolution_tpu.volumes import driver

                key = entry.key
                D, H, W = key.shape
                probe = np.zeros((VOLUME_FIELDS, D, H, W), np.float32)
                xs, valid_hw = driver.prepare_volume(
                    probe, self.mesh, key.boundary)
                _, block_hw, _ = driver._geometry(
                    (VOLUME_FIELDS, D, H, W), self.mesh, key.boundary)
                fn = driver.converge_chunk_fn(
                    self.mesh, key.filter_name, n, D, valid_hw,
                    block_hw, key.fuse, key.boundary)
                jax.block_until_ready(fn(xs)[1])
                entry.converge_fns[n] = fn
                entry.compiles += 1
                with self._lock:
                    self.stats["compiles"] += 1
                return fn
            import jax

            from parallel_convolution_tpu.parallel import step as step_lib

            key = entry.key
            filt = get_filter(key.filter_name)
            probe = np.zeros(key.shape, np.float32)
            launch = entry.launch
            xs, valid_hw, block_hw = step_lib._prepare(probe, self.mesh,
                                                       launch)
            fn = step_lib._build_converge_chunk(
                self.mesh, filt, n, key.quantize, valid_hw, block_hw,
                launch.backend, launch.boundary, launch.fuse, launch.tile,
                launch.overlap, launch.col_mode)
            jax.block_until_ready(fn(xs)[1])  # compile NOW: the stream's
            #                                   first chunk must not pay it
            entry.converge_fns[n] = fn
            entry.compiles += 1
            with self._lock:
                self.stats["compiles"] += 1
            return fn

    def run_converge(self, key: EngineKey, image: np.ndarray, *,
                     tol: float, max_iters: int, check_every: int,
                     start_done: int = 0, start_wu: float = 0.0,
                     start_diff: float = float("inf")):
        """Progressive run-to-convergence through the warm cache.

        ``image`` is ONE (C, H, W) f32 field; ``key.iters`` should equal
        ``check_every`` (the chunk program's compile identity — the
        service's converge keying does this).  A generator yielding
        ``(image_f32, done, diff, work_units)`` per chunk exactly like
        ``step.sharded_converge_stream``, but with the chunk executables
        cached on the warm entry (same LRU / single-flight / degrade
        machinery as the batch path) so a stream of convergence jobs for
        one config compiles once.  ``work_units`` is the fine-grid work
        spent so far — for jacobi the iteration count itself; for
        ``key.solver == "multigrid"`` (one yield per V-CYCLE, ``done``
        counting cycles, ``diff`` the fine-grid residual norm) the
        pixel-weighted per-level accounting that makes the two solvers
        comparable under one budget.

        ``start_done``/``start_wu`` seed a RESUMED job (round 18):
        ``image`` is then a mid-stream field from a resume token, the
        iteration/cycle count continues from ``start_done``, and
        ``max_iters`` keeps meaning the job's TOTAL budget — the
        resumed stream only spends what the token hasn't.  Tokens are
        minted on ``check_every`` (resp. V-cycle) boundaries, so the
        remaining chunk sizes are exactly the uninterrupted run's —
        which is why the resumed final row is byte-identical (asserted
        in tests/test_chaos.py; crop + zero-re-pad is bit-exact on any
        grid, so it holds even resuming onto a different mesh).

        A mid-stream mesh reshape raises the same stale-grid ValueError
        as :meth:`run_batch` — the service turns it into a typed,
        retryable ``resharding`` row after the best-so-far snapshots
        already streamed out.
        """
        import jax.numpy as jnp

        from parallel_convolution_tpu.parallel import step as step_lib

        entry = self.entry(key)
        if key.rank == 3:
            # ``image`` is one (2, D, H, W) float32 volume; the chunk
            # executables come from volumes.driver through the same
            # warm-entry cache, and the chunk math is identical — so
            # resume tokens minted on check_every boundaries replay
            # byte-stably exactly like rank 2.
            yield from self._run_volume_converge(
                entry, key, image, tol=tol, max_iters=max_iters,
                check_every=check_every, start_done=start_done,
                start_diff=start_diff)
            return
        filt = get_filter(key.filter_name)
        if tuple(image.shape) != key.shape:
            raise ValueError(
                f"image shape {tuple(image.shape)} does not match key "
                f"{key.shape}")
        start_done, start_wu = int(start_done), float(start_wu)
        if float(start_diff) < tol:
            # The token already met the tolerance (the dead stream died
            # between its last chunk and the final row): nothing left to
            # run — the caller emits the final row from the token.
            return
        if key.solver == "multigrid":
            # The V-cycle's level programs are module-level lru-cached
            # (solvers.multigrid) on (mesh, filter, geometry, backend) —
            # a stream of jobs for one config compiles once, exactly the
            # warm-cache property the chunk path has.  The stale-grid
            # guard runs per cycle: the generator reads self.grid() each
            # readback, so a mid-stream reshape surfaces as the same
            # typed ValueError, never an execution on the wrong mesh.
            from parallel_convolution_tpu.solvers import multigrid

            entry.mg_levels = len(multigrid.plan_levels(
                self.mesh, image.shape[1:], filt.radius, key.boundary,
                key.mg_levels))
            budget = float(max_iters) - start_wu
            if budget <= 0:
                return
            stream = multigrid.mg_converge_stream(
                np.ascontiguousarray(image, dtype=np.float32), filt,
                tol=tol, max_iters=budget, mesh=self.mesh,
                quantize=key.quantize, backend=entry.effective_backend,
                storage=key.storage, boundary=key.boundary,
                tile=key.tile, overlap=entry.effective_overlap,
                mg_levels=key.mg_levels,
                col_mode=entry.effective_col_mode)
            for out, cycles, residual, wu in stream:
                if key.grid != self.grid():
                    raise ValueError(
                        f"stale key grid {key.grid}: engine mesh is now "
                        f"{self.grid()} (resharded mid-process)")
                yield (out, cycles + start_done, residual,
                       round(wu + start_wu, 3))
            return
        xs, valid_hw, _ = step_lib._prepare(
            np.ascontiguousarray(image, dtype=np.float32), self.mesh,
            entry.launch)
        check_every, max_iters = int(check_every), int(max_iters)
        done, diff = start_done, float("inf")   # start_diff >= tol here:
        #                                         the chunk loop re-reads
        #                                         its own residual
        while done < max_iters and diff >= tol:
            if key.grid != self.grid():
                raise ValueError(
                    f"stale key grid {key.grid}: engine mesh is now "
                    f"{self.grid()} (resharded mid-process)")
            n = min(check_every, max_iters - done)
            fn = self._converge_fn(entry, n)
            xs, d = fn(xs)
            diff = float(d)   # the readback fences the chunk
            done += n
            yield (np.asarray(xs[:, : valid_hw[0], : valid_hw[1]]
                              .astype(jnp.float32)), done, diff, float(done))

    def _run_volume_converge(self, entry: _Entry, key: EngineKey,
                             volume: np.ndarray, *, tol: float,
                             max_iters: int, check_every: int,
                             start_done: int = 0,
                             start_diff: float = float("inf")):
        """The rank-3 arm of :meth:`run_converge`: yields
        ``(volume_f32, done, diff, work_units)`` per chunk, volumes at
        the valid extent."""
        from parallel_convolution_tpu.utils.config import VOLUME_FIELDS
        from parallel_convolution_tpu.volumes import driver

        expect = (VOLUME_FIELDS,) + key.shape
        if tuple(volume.shape) != expect:
            raise ValueError(
                f"volume shape {tuple(volume.shape)} does not match "
                f"key (want {expect})")
        if float(start_diff) < tol:
            return
        xs, valid_hw = driver.prepare_volume(
            np.ascontiguousarray(volume, dtype=np.float32), self.mesh,
            key.boundary)
        check_every, max_iters = int(check_every), int(max_iters)
        done, diff = int(start_done), float("inf")
        while done < max_iters and diff >= tol:
            if key.grid != self.grid():
                raise ValueError(
                    f"stale key grid {key.grid}: engine mesh is now "
                    f"{self.grid()} (resharded mid-process)")
            n = min(check_every, max_iters - done)
            fn = self._converge_fn(entry, n)
            xs, d = fn(xs)
            diff = float(d)   # the readback fences the chunk
            done += n
            out = np.asarray(xs)[:, :, : valid_hw[0], : valid_hw[1]]
            yield (out.astype(np.float32, copy=False), done, diff,
                   float(done))

    # -- introspection ------------------------------------------------------
    def warm_key_count(self) -> int:
        """Resident warm keys (the ``/readyz`` payload's ``warm_keys``
        — one of the autoscaler's placement signals)."""
        with self._lock:
            return len(self._entries)

    def degraded(self) -> list[dict]:
        """Distinct requested→effective backend downgrades among resident
        entries — the 'current degrade tier' surface ``/readyz`` reports
        (a degraded service still serves; readiness reports it rather
        than failing on it)."""
        with self._lock:
            pairs = sorted({(k.backend, e.effective_backend)
                            for k, e in self._entries.items()
                            if e.effective_backend != k.backend})
        return [{"requested": req, "effective": eff} for req, eff in pairs]

    def snapshot(self) -> dict:
        """Stats + resident keys, for /stats and the loadgen row."""
        from parallel_convolution_tpu.parallel import channels

        with self._lock:
            return {
                "stats": dict(self.stats),
                # Persistent-channel reuse evidence: descriptor-plan
                # builds vs cache hits, process-global (the
                # --channels-smoke leg asserts builds stay flat across
                # a warm key's request stream).
                "channels": channels.stats(),
                "capacity": self.capacity,
                "grid": "x".join(str(v) for v in self.grid()),
                "resident": [
                    {"filter": k.filter_name, "shape": list(k.shape),
                     "backend": k.backend,
                     "effective_backend": e.effective_backend,
                     "fuse": k.fuse,
                     "tile": list(k.tile) if k.tile else None,
                     "overlap": e.effective_overlap,
                     "col_mode": e.effective_col_mode,
                     "plan_source": e.plan_source,
                     "predicted_gpx_per_chip": e.predicted_gpx,
                     "batch_sizes": sorted(e.fns),
                     # Per-key compile ledger (r17): the warm-placement
                     # gate asserts a pre-warmed shard holds this flat.
                     "compiles": e.compiles,
                     "iters": k.iters}
                    for k, e in self._entries.items()
                ],
            }
