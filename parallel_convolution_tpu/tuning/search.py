"""Candidate enumeration + measured search over the stencil knob space.

The reference repo's whole point is choosing the decomposition that fits
the hardware (MPI grid x OpenMP tile); this module is that choice made
by machine for the TPU port's knobs — backend tier, temporal-fusion
depth, Pallas kernel tile — in the AutoTVM/Halide-scheduler shape
(PAPERS.md): a deterministic *legal* candidate space, an analytical
prior (``tuning.costmodel``) that ranks it, and measured refinement
(``utils.bench.bench_iterate``) over only the model's shortlist, so a
full tune is O(dozens) of compiles rather than the knob product.

Legality rules are the kernels' own constraints, enumerated rather than
discovered as compile errors:

* tiles: multiples of the storage dtype's (sublane, 128) HBM tiling,
  within the Mosaic scoped-VMEM budget for the kernel form (the 2D tap
  loop keeps ~k^2 live (th, tw) f32 temporaries; the separable form
  reuses one pair — DESIGN.md round-1 lesson 2);
* fuse: ``block >= r*T`` (every backend), plus ``r*T <= sublane`` when
  the RDMA tier would auto-select its tiled kernel (the aligned band
  carries every live ghost row);
* separable tiers only where they are byte-safe: an exactly rank-1
  filter, and only in quantize mode with dyadic taps (the same rule
  ``resilience.degrade`` applies when walking *out* of them) — auto
  must never pick a backend that changes bytes.

``tune(..., dry_run=True)`` never touches a device: it returns the
model-ranked best with ``source="predicted"`` — runnable on any CPU,
which is what the tier-1 ``--tuning-smoke`` leg exercises.
"""

from __future__ import annotations

import dataclasses

from parallel_convolution_tpu.tuning import costmodel
from parallel_convolution_tpu.tuning.plans import Plan, Workload

__all__ = ["Candidate", "enumerate_candidates", "rank", "tune",
           "TuneResult"]

# Tile menu swept on silicon by the round-1 tuner; legality filters trim
# it per workload.  None = the per-kernel tuned default, always legal.
TILE_MENU = (None, (128, 512), (256, 256), (256, 512), (256, 1024),
             (512, 512), (512, 1024), (1024, 512))

FUSE_MENU = (1, 2, 4, 8, 16, 32)

# Model-tie preference: earlier wins.  Compiled-XLA normative path first
# among equals so a flat model (e.g. all-CPU) resolves to 'shifted'.
_PREFERENCE = ("shifted", "xla_conv", "separable", "pallas_sep", "pallas",
               "pallas_rdma")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the knob space:
    (backend, fuse, tile, overlap, col_mode)."""

    backend: str
    fuse: int = 1
    tile: tuple[int, int] | None = None
    overlap: bool = False  # interior-first overlapped halo pipeline
    #                        (RDMA tier only; costmodel.overlap_legal)
    col_mode: str = "packed"  # column-slab transport (persistent tiers
    #                           only; both modes byte-identical, the
    #                           model prices the descriptor trade)


def _sep_byte_safe(w: Workload) -> bool:
    """Separable tiers are candidates only where their rank-1 rounding
    order is provably byte-identical (degrade.py's rule, applied at
    selection time instead of fallback time)."""
    return w.separable and w.quantize and w.dyadic


def _legal_backends(w: Workload) -> list[str]:
    out = ["shifted", "xla_conv", "pallas", "pallas_rdma"]
    if _sep_byte_safe(w):
        out += ["separable", "pallas_sep"]
    return out


def _legal_fuses(w: Workload, backend: str, menu,
                 strict: bool = False) -> list[int]:
    """``strict=True`` (explicitly-pinned menus) returns [] when nothing
    survives — the pin must die loudly upstream, never be silently
    remeasured as fuse=1; the default menu falls back to the always-
    legal unfused depth."""
    bh, bw = w.block_hw
    # Convergence workloads fuse at most their n-1 pre-pair iterations
    # (step._build_converge clamps to check_every - 1); enumerating past
    # that would tune a depth the runner can never execute.
    ce = getattr(w, "check_every", None)
    fuse_cap = None if ce is None else max(1, int(ce) - 1)
    out = []
    for T in menu:
        T = int(T)
        if T < 1 or w.radius * T > min(bh, bw):
            continue
        if fuse_cap is not None and T > fuse_cap:
            continue
        if backend == "pallas_rdma":
            if costmodel.rdma_is_tiled(w.shape, w.block_hw, w.radius, T,
                                       w.storage):
                sub = costmodel.SUBLANE[w.storage]
                if (w.radius * T > min(sub, costmodel.LANE)
                        or bh < sub or bw < costmodel.LANE):
                    continue
        out.append(T)
    return out or ([] if strict else [1])


def _tile_vmem_ok(w: Workload, backend: str, tile: tuple[int, int],
                  fuse: int = 1) -> bool:
    """Scoped-VMEM estimate for a candidate (tile, fuse) point.

    2D tap loop: ~(k^2 + 2) live (th, tw) f32 temporaries (the unrolled
    shifted multiply-add chain) — the form that failed Mosaic compile at
    1024x512 f32 (25.3 MB vs the 16 MB bound).  Separable: one
    (th+k-1, tw) + one (th, tw) accumulator.  Both forms additionally
    hold the double-buffered input-window pair, which GROWS with the
    fusion depth (2*r*T rim per side) — legality is per (tile, fuse)
    point, not per tile, or a deep-fused candidate near the bound would
    pass at the fuse=1 estimate and fail Mosaic compile at launch.
    Estimates err permissive-by-~20%; the degrade walk (and measured
    search) catches what slips through.
    """
    th, tw = tile
    k = w.taps_k
    d = w.radius * max(1, int(fuse))
    window = 2 * (th + 2 * d) * (tw + 2 * d) * costmodel.STORAGE_BYTES[
        w.storage]
    if backend == "pallas_sep" and _sep_byte_safe(w):
        live = ((th + k - 1) * tw + th * tw) * 4 + window
    else:
        live = (k * k + 2) * th * tw * 4 + window
    return live <= costmodel.SCOPED_VMEM_BYTES


def _legal_tiles(w: Workload, backend: str, menu,
                 strict: bool = False, fuse: int = 1) -> list:
    """``strict`` as in :func:`_legal_fuses` — a pinned tile that fails
    legality yields [] (loud upstream error), never a silent None.
    Non-Pallas backends have no tile knob, so any menu degenerates to
    [None] there (the value is ignored by the kernels)."""
    if backend not in costmodel.PALLAS_BACKENDS:
        return [None]
    sub = costmodel.SUBLANE[w.storage]
    bh, bw = w.block_hw
    out = []
    for t in menu:
        if t is None:
            out.append(None)
            continue
        th, tw = (int(v) for v in t)
        if th % sub or tw % costmodel.LANE:
            continue  # HBM DMA slices must align to (sublane, 128)
        if th > max(bh, sub) or tw > max(bw, costmodel.LANE):
            continue  # larger than the block: degenerate duplicate of None
        if not _tile_vmem_ok(w, backend, (th, tw), fuse):
            continue
        out.append((th, tw))
    return out or ([] if strict else [None])


def _legal_overlaps(w: Workload, backend: str, fuse: int,
                    overlap: bool | None) -> list[bool]:
    """Overlap options for one (backend, fuse) point.

    ``overlap`` is a *request*, not a hard pin: an explicit True is
    clamped to legality (the serialized form is always available, and
    every artifact stamps the RESOLVED value) — unlike fuse/tile pins,
    which die loudly, because overlap legality depends on the backend
    the tuner is still choosing, so a hard pin would empty every
    non-RDMA branch of the space.

    Interpreted-Pallas platforms enumerate only the serialized form
    (unless the byte-proof env hatch is armed): the dispatch layer
    force-serializes overlap there, so an overlap=True candidate would
    MEASURE the serialized executable — two identical twins burning the
    measurement budget, and a plan stamped overlap=True whose
    measured_gpx never ran the overlapped program.
    """
    import os

    from parallel_convolution_tpu.utils.config import OVERLAP_INTERPRET_ENV

    legal = costmodel.overlap_legal(backend, w.grid, w.block_hw, w.radius,
                                    fuse)
    if (legal and costmodel.hardware_for(
            w.platform, w.device_kind).interpret_pallas
            and not os.environ.get(OVERLAP_INTERPRET_ENV)):
        legal = False
    if overlap is None:
        return [False, True] if legal else [False]
    return [bool(overlap) and legal]


def _legal_col_modes(w: Workload, backend: str,
                     col_mode: str | None) -> list[str]:
    """Column-transport options for one backend.

    Only persistent-capable tiers with a REAL remote column axis have
    the A/B (both transports compile the identical statically-elided
    program otherwise — enumerating twins would burn the measurement
    budget on duplicates); everywhere else the knob is inert and
    normalizes to the canonical "packed".  Like overlap, an explicit
    request is clamped rather than dying: both modes are byte-identical,
    and legality depends on the backend the tuner is still choosing.
    """
    if (backend not in costmodel.PERSISTENT_BACKENDS
            or w.grid[1] <= 1):
        return ["packed"]
    if col_mode in (None, "auto"):
        return ["packed", "strided"]
    return [col_mode if col_mode in costmodel.COL_MODES else "packed"]


def enumerate_candidates(w: Workload, backends=None, fuses=None,
                         tiles=None, overlap: bool | None = None,
                         col_mode: str | None = None,
                         ) -> list[Candidate]:
    """The deterministic legal candidate list for one workload.

    ``backends``/``fuses``/``tiles`` pin a sub-space (an explicitly
    passed knob is honored verbatim; legality still filters fuse depth
    so an impossible pin dies here with an empty-space error rather
    than deep inside a kernel launch).  ``overlap`` (None = enumerate
    both where legal) is a clamped request — see :func:`_legal_overlaps`
    — and ``col_mode`` likewise (None/'auto' = enumerate both where the
    transport exists; see :func:`_legal_col_modes`).
    """
    out = []
    for b in (backends if backends is not None else _legal_backends(w)):
        for T in _legal_fuses(w, b, fuses if fuses is not None
                              else FUSE_MENU, strict=fuses is not None):
            for t in _legal_tiles(w, b, tiles if tiles is not None
                                  else TILE_MENU, strict=tiles is not None,
                                  fuse=T):
                for ov in _legal_overlaps(w, b, T, overlap):
                    for cm in _legal_col_modes(w, b, col_mode):
                        out.append(Candidate(b, T, t, ov, cm))
    if not out:
        raise ValueError(
            f"no legal candidates for {w.filter_name} {w.shape} on grid "
            f"{w.grid} (backends={backends}, fuses={fuses}, tiles={tiles})")
    return out


def predict(w: Workload, c: Candidate,
            hw: costmodel.HardwareModel | None = None) -> float:
    """Model seconds/px/iter for one candidate (ranking unit)."""
    hw = hw or costmodel.hardware_for(w.platform, w.device_kind)
    return costmodel.predict_seconds_per_px_iter(
        c.backend, w.storage, c.fuse, c.tile, w.shape, w.block_hw, w.grid,
        w.taps_k, w.separable, w.quantize, hw, overlap=c.overlap,
        col_mode=c.col_mode)


def rank(w: Workload, candidates,
         hw: costmodel.HardwareModel | None = None,
         ) -> list[tuple[float, Candidate]]:
    """Candidates sorted best-first by predicted time, deterministically
    (ties break on the backend preference order, then the knob tuple)."""
    hw = hw or costmodel.hardware_for(w.platform, w.device_kind)

    def sort_key(pc):
        t, c = pc
        pref = (_PREFERENCE.index(c.backend)
                if c.backend in _PREFERENCE else len(_PREFERENCE))
        # overlap last: on a model tie (exchange fully hidden OR zero)
        # the serialized form wins — never pipeline for a predicted 0.
        # col_mode last of all: packed (the canonical label) wins ties.
        return (t, pref, c.fuse, c.tile or (0, 0), c.overlap, c.col_mode)

    return sorted(((predict(w, c, hw), c) for c in candidates),
                  key=sort_key)


@dataclasses.dataclass
class TuneResult:
    """A tune's verdict plus its evidence rows (one per measured point)."""

    plan: Plan
    workload: Workload
    rows: list[dict]


def measure(w: Workload, c: Candidate, mesh, *, iters: int = 8,
            reps: int = 2) -> dict:
    """One measured point: a ``bench_iterate`` row for this candidate
    (resolved tile/fuse stamped by bench itself), plus the model's
    prediction for measured-vs-predicted visibility."""
    from parallel_convolution_tpu.ops.filters import get_filter
    from parallel_convolution_tpu.utils import bench

    # At least one full fused chunk: bench clamps fuse to iters, so a
    # fuse=32 candidate measured at iters=8 would silently price fuse=8
    # (and its row would say so — but the tuner must price the ACTUAL
    # candidate).  Per-iteration normalization keeps rows comparable.
    row = bench.bench_iterate(
        w.shape[1:], get_filter(w.filter_name), max(iters, c.fuse),
        mesh=mesh, channels=w.shape[0], backend=c.backend,
        quantize=w.quantize, storage=w.storage, fuse=c.fuse,
        boundary=w.boundary, reps=reps, tile=c.tile,
        overlap=c.overlap,
        col_mode=c.col_mode)
    row["predicted_gpx_per_chip"] = round(
        costmodel.predict_gpx_per_chip(predict(w, c)), 3)
    return row


def tune(w: Workload, mesh=None, *, dry_run: bool = False,
         backends=None, fuses=None, tiles=None, overlap: bool | None = None,
         col_mode: str | None = None,
         iters: int = 8,
         reps: int = 2, max_measure: int = 8,
         prune_factor: float = 4.0) -> TuneResult:
    """Tune one workload: rank the legal space, optionally measure.

    ``dry_run=True`` (or ``mesh=None``) returns the model's pick with
    ``source="predicted"`` and zero device work.  Otherwise the top
    ``max_measure`` candidates within ``prune_factor`` of the model-best
    predicted time are benched (each one compile + a few timed reps — a
    full tune is O(dozens) of compiles, not the knob product) and the
    best measured Gpx/s/chip wins, ``source="measured"``.  Candidates
    that fail to compile/launch are recorded as error rows and skipped —
    the tuner prices what works.
    """
    ranked = rank(w, enumerate_candidates(w, backends, fuses, tiles,
                                          overlap=overlap,
                                          col_mode=col_mode))
    best_t, best_c = ranked[0]
    predicted_gpx = costmodel.predict_gpx_per_chip(best_t)
    if dry_run or mesh is None:
        return TuneResult(
            Plan(best_c.backend, best_c.fuse, best_c.tile,
                 source="predicted",
                 predicted_gpx=round(predicted_gpx, 3),
                 overlap=best_c.overlap, col_mode=best_c.col_mode),
            w, rows=[])
    rows: list[dict] = []
    measured: list[tuple[float, Candidate, float]] = []
    shortlist = [(t, c) for t, c in ranked
                 if t <= best_t * prune_factor][:max(1, int(max_measure))]
    for t, c in shortlist:
        try:
            row = measure(w, c, mesh, iters=iters, reps=reps)
        except Exception as e:  # noqa: BLE001 — an illegal point is data
            rows.append({"backend": c.backend, "fuse": c.fuse,
                         "tile": (f"{c.tile[0]}x{c.tile[1]}" if c.tile
                                  else None),
                         "overlap": c.overlap, "col_mode": c.col_mode,
                         "error": repr(e)[:200]})
            continue
        rows.append(row)
        measured.append((row["gpixels_per_s_per_chip"], c,
                         row["predicted_gpx_per_chip"]))
    if not measured:
        raise RuntimeError(
            f"every shortlisted candidate failed to measure "
            f"({len(shortlist)} tried); see rows for errors")
    measured.sort(key=lambda m: (-m[0], _PREFERENCE.index(m[1].backend)
                                 if m[1].backend in _PREFERENCE
                                 else len(_PREFERENCE)))
    gpx, c, pred = measured[0]
    return TuneResult(
        Plan(c.backend, c.fuse, c.tile, source="measured",
             predicted_gpx=round(pred, 3), measured_gpx=round(gpx, 3),
             overlap=c.overlap, col_mode=c.col_mode),
        w, rows=rows)
