"""Exchange/overlap attribution: halo bytes and exchange-vs-compute split.

ROADMAP items 1 and 3 are judged on instrumentation this module owns:

* "Persistent and Partitioned MPI for Stencil Communication" (PAPERS.md)
  demonstrates its overlap wins through per-phase exchange-vs-interior
  timing — so every step/bench/serving artifact now carries an
  ``exchange_fraction`` attribution (the roofline model's exchange term
  over its total, a pure function of the decomposition);
* "Efficient Process-to-Node Mapping Algorithms for Stencil
  Computations" (PAPERS.md) validates layouts via per-direction halo
  *byte* accounting — :func:`halo_bytes_per_round` is that accounting as
  an analytic formula of (grid, block, radius, fuse, dtype, boundary),
  tested against an independent derivation in ``tests/test_obs.py``.

The byte formula mirrors ``parallel/halo.halo_exchange`` exactly:

* phase 1 (rows): each sending device moves a ``channels × d × bw`` slab
  per direction, ``d = radius*fuse`` (temporal fusion widens the ghost
  band); with zero boundaries only ``R-1`` of the ``R`` rows send each
  way, with periodic all ``R`` do — and a 1-long axis moves NOTHING
  (``halo._shift`` short-circuits to zeros/self, no collective);
* phase 2 (cols): slabs are cut from the already row-padded block, so
  their height is ``bh + 2d`` — the corner bytes ride the column phase,
  which is exactly how the two-hop corner propagation pays for skipping
  the reference's diagonal messages.

The same ghost bands (same depth, same directions) are what the RDMA
kernels DMA in-kernel, so the accounting is backend-independent by
construction: it prices the *decomposition*, not the transport.

jax-free: everything here is arithmetic over ints, reusing the tuning
cost model's calibrated constants for the time split.
"""

from __future__ import annotations

import time

from parallel_convolution_tpu.obs import events, metrics, trace
from parallel_convolution_tpu.tuning import costmodel

__all__ = [
    "exchange_rounds", "halo_bytes_per_round", "halo_bytes_total",
    "predicted_exchange_fraction", "predicted_exchange_split",
    "record_drift", "record_halo", "record_step",
    "volume_face_bytes_per_round",
]

DIRECTIONS = ("north", "south", "east", "west")


def halo_bytes_per_round(grid: tuple[int, int], block_hw: tuple[int, int],
                         radius: int, fuse: int, channels: int,
                         storage: str, boundary: str = "zero") -> dict:
    """Per-direction bytes crossing device links in ONE exchange round,
    summed over the whole mesh.

    A "round" is one ``halo_exchange`` at ghost depth ``d = radius*fuse``
    (the fused-chunk exchange).  Directions name where the data travels:
    ``south`` = toward higher row index, ``east`` = toward higher column
    index.  Zero-boundary edges send nothing outward (there is no
    neighbor); periodic boundaries close the ring — except on a 1-long
    axis, where the wrap is the identity and no collective exists.
    """
    R, C = (int(g) for g in grid)
    bh, bw = (int(b) for b in block_hw)
    d = int(radius) * max(1, int(fuse))
    B = costmodel.STORAGE_BYTES[storage]
    periodic = boundary == "periodic"
    row_senders = (R if periodic else R - 1) if R > 1 else 0
    col_senders = (C if periodic else C - 1) if C > 1 else 0
    row_slab = channels * d * bw * B          # phase 1: (C, d, bw)
    col_slab = channels * d * (bh + 2 * d) * B  # phase 2: row-padded height
    out = {
        "south": row_senders * C * row_slab,
        "north": row_senders * C * row_slab,
        "east": col_senders * R * col_slab,
        "west": col_senders * R * col_slab,
    }
    out["total"] = sum(out.values())
    return out


def volume_face_bytes_per_round(grid: tuple[int, int],
                                block_hw: tuple[int, int], depth: int,
                                radius: int, fuse: int, fields: int = 2,
                                storage: str = "f32",
                                boundary: str = "zero") -> dict:
    """Per-direction bytes of ONE rank-3 6-face ghost exchange.

    The ±D faces are a LOCAL pad (the depth axis is resident —
    ``volumes.halo3``), so only the ±H/±W face slabs cross links, and
    each slab carries the whole depth-padded field column: the rank-2
    slab arithmetic at an effective channel count of
    ``fields * (depth + 2d)``.  Same direction naming, same
    zero-vs-periodic sender rule, same 1-long-axis elision as
    :func:`halo_bytes_per_round`."""
    d = int(radius) * max(1, int(fuse))
    ch = max(1, int(fields)) * (max(1, int(depth)) + 2 * d)
    return halo_bytes_per_round(grid, block_hw, radius, fuse, ch,
                                storage, boundary)


def exchange_rounds(iters: int, fuse: int) -> tuple[int, int]:
    """``(full_rounds, tail_iters)`` of the fused iteration schedule: the
    runner exchanges once per ``fuse``-iteration chunk plus once for the
    remainder chunk (at its own shallower depth)."""
    fuse = max(1, min(int(fuse), max(1, int(iters))))
    return int(iters) // fuse, int(iters) % fuse


def halo_bytes_total(grid, block_hw, radius: int, fuse: int, iters: int,
                     channels: int, storage: str,
                     boundary: str = "zero") -> dict:
    """Per-direction bytes for a whole ``iters``-iteration run — full
    fused rounds at depth ``radius*fuse`` plus the tail round at its own
    depth (``radius * (iters % fuse)``), exactly the schedule
    ``step._build_iterate`` compiles."""
    full, rem = exchange_rounds(iters, fuse)
    total = {d: 0 for d in (*DIRECTIONS, "total")}
    per = halo_bytes_per_round(grid, block_hw, radius, fuse, channels,
                               storage, boundary)
    for k in total:
        total[k] += full * per[k]
    if rem:
        tail = halo_bytes_per_round(grid, block_hw, radius, rem, channels,
                                    storage, boundary)
        for k in total:
            total[k] += tail[k]
    total["rounds"] = full + (1 if rem else 0)
    return total


def predicted_exchange_split(
        grid, block_hw, radius: int, fuse: int, *, backend: str,
        storage: str, shape: tuple[int, int, int],
        tile: tuple[int, int] | None = None, quantize: bool = True,
        separable: bool = False, platform: str = "cpu",
        device_kind: str = "", overlap: bool = False,
        col_mode: str = "packed") -> dict:
    """Exchange-vs-compute attribution of one iteration's roofline time,
    overlap-adjusted.

    Returns::

      {"exchange_fraction":        exposed exchange / total wall,
       "exchange_hidden_fraction": hidden exchange / total exchange,
       "exchange_hidden_of_total": hidden exchange / total wall,
       "overlap":                  the caller's compiled-form knob}

    ``overlap`` is reported back VERBATIM (callers pass the knob the
    executable compiled with, so events and rows agree by construction);
    the max() *adjustment* applies only where the pipeline can actually
    hide bytes (``costmodel.overlap_legal`` — a degenerate all-rim block
    or a 1x1 grid computes in serialized order even inside the
    overlapped program, so it is priced serialized with hidden = 0).

    Serialized arithmetic: total = compute + exchange, nothing hidden,
    ``exchange_fraction`` is exactly the pre-overlap series.
    Overlapped: the interior-first pipeline rides the exchange under
    the compute roof, so ``hidden = min(exchange, compute)`` and only
    the remainder is exposed over ``total = max(compute, exchange)`` —
    the "hidden vs. exposed exchange time" reading the overlapped-halo
    ROADMAP item is judged by.  Pure model attribution: the interpret
    penalty scales all terms, so the fractions are penalty-invariant; a
    1x1 grid is exactly 0 / 0.
    """
    hw = costmodel.hardware_for(platform, device_kind)
    T = max(1, int(fuse))
    k = 2 * int(radius) + 1
    ov = bool(overlap) and costmodel.overlap_legal(
        backend, tuple(grid), tuple(block_hw), int(radius), T)
    out = {"exchange_fraction": 0.0, "exchange_hidden_fraction": 0.0,
           "exchange_hidden_of_total": 0.0, "overlap": bool(overlap)}
    persistent = backend in costmodel.PERSISTENT_BACKENDS
    ex = costmodel.exchange_seconds_per_px_iter(
        tuple(grid), tuple(block_hw), int(radius), T, storage, hw,
        persistent=persistent,
        col_mode=col_mode if persistent else "packed")
    if ex == 0.0:
        return out
    tile_eff = costmodel.effective_tile(backend, tile)
    rim_tile = tile_eff if tile_eff is not None else tuple(block_hw)
    if backend == "pallas_rdma" and not costmodel.rdma_is_tiled(
            tuple(shape), tuple(block_hw), int(radius), T, storage,
            col_mode=col_mode, grid=tuple(grid)):
        rim_tile = tuple(block_hw)
    sep = separable and backend in ("separable", "pallas_sep")
    t_hbm = costmodel.hbm_bytes_per_px_iter(
        backend, storage, T, tile, tuple(block_hw), int(radius),
        tuple(shape)) / (hw.hbm_gbps * 1e9)
    t_flop = costmodel.flops_per_px_iter(
        k, sep, quantize, T, rim_tile, int(radius)) / (hw.flop_gops * 1e9)
    roof = max(t_hbm, t_flop)
    if ov:
        hidden = min(ex, roof)
        exposed = ex - hidden
        total = max(roof, ex)
        out["exchange_hidden_fraction"] = min(1.0, hidden / ex)
        if total > 0:
            out["exchange_hidden_of_total"] = min(1.0, hidden / total)
    else:
        exposed, total = ex, roof + ex
    if total > 0:
        out["exchange_fraction"] = min(1.0, exposed / total)
    return out


def predicted_exchange_fraction(
        grid, block_hw, radius: int, fuse: int, *, backend: str,
        storage: str, shape: tuple[int, int, int],
        tile: tuple[int, int] | None = None, quantize: bool = True,
        separable: bool = False, platform: str = "cpu",
        device_kind: str = "", overlap: bool = False) -> float:
    """The (exposed) exchange share of one iteration, in [0, 1] — the
    ``exchange_fraction`` member of :func:`predicted_exchange_split`,
    kept as the scalar surface existing callers/series use.  With
    ``overlap=False`` the values are identical to the pre-overlap
    series (compute + exchange decomposition)."""
    return predicted_exchange_split(
        grid, block_hw, radius, fuse, backend=backend, storage=storage,
        shape=shape, tile=tile, quantize=quantize, separable=separable,
        platform=platform, device_kind=device_kind,
        overlap=overlap)["exchange_fraction"]


# -- the step-level recorders ----------------------------------------------
# The fenced callers (serving/engine.run_batch, the convergence path, the
# bench rows) call record_step: wall, model-attributed split, counters.
# parallel/step.iterate_prepared dispatches asynchronously and feeds only
# the counters, through record_halo, from what its runner's trace built.

def _m():
    """Metric handles, created lazily through the global registry (so a
    registry reset in tests re-creates them on next use)."""
    return (
        metrics.histogram(
            "pctpu_step_seconds",
            "wall of one compiled iterate call (all fused blocks)",
            ("backend",)),
        metrics.counter(
            "pctpu_exchange_seconds_total",
            "model-attributed EXPOSED exchange share of step walls",
            ("backend",)),
        metrics.counter(
            "pctpu_compute_seconds_total",
            "model-attributed compute share of step walls", ("backend",)),
        metrics.counter(
            "pctpu_exchange_hidden_seconds_total",
            "model-attributed exchange time hidden under compute by the "
            "overlapped pipeline", ("backend",)),
        metrics.counter(
            "pctpu_halo_bytes_total",
            "ghost-band bytes moved, per direction",
            ("backend", "direction")),
        metrics.counter(
            "pctpu_halo_rounds_total", "halo exchange rounds executed",
            ("backend",)),
        metrics.counter(
            "pctpu_iterations_total", "stencil iterations executed",
            ("backend",)),
    )


def record_step(*, backend: str, grid, block_hw, radius: int, fuse: int,
                iters: int, channels: int, storage: str, boundary: str,
                wall_s: float | None, shape, quantize: bool = True,
                tile=None, platform: str = "cpu", device_kind: str = "",
                source: str = "step", overlap: bool = False,
                col_mode: str = "packed",
                mg_level: int | None = None) -> dict | None:
    """Record one compiled-iterate call: wall, halo bytes, exchange split.

    ``col_mode`` (round 16) stamps the resolved column-slab transport
    into the exchange event; the per-slab wait series
    ``pctpu_halo_slab_wait_seconds{direction, which}`` attributes the
    exchange wall across the four slab channels by their byte share,
    split exposed-vs-hidden — the partitioned-completion analogue of
    the r12 hidden/exposed split, per slab instead of per phase.

    ``mg_level`` (round 15) attributes the call to one multigrid grid
    level: the exchange event carries the level and the sweep counter
    gains the ``pctpu_mg_level`` label, so per-level exchange/compute
    cost is a label filter away (level 0 = the fine grid).

    ``wall_s=None`` means the caller dispatched asynchronously and has no
    honest device wall (``iterate_prepared`` — fencing there would
    silently serialize the library's async iterate path): the byte/round
    counters and the event still land, but the wall histogram and the
    exchange/compute second split are skipped rather than fed a
    dispatch-only wall.  Callers that already fence (bench, the serving
    device phase, the convergence path's count readback) pass the real
    wall.

    Returns the attribution dict (halo bytes + fraction) for callers that
    stamp rows, or None when obs is disabled (nothing computed — the
    arithmetic itself is the overhead being avoided).
    """
    if not metrics.enabled():
        return None
    sep = backend in ("separable", "pallas_sep")
    by = halo_bytes_total(grid, block_hw, radius, fuse, iters, channels,
                          storage, boundary)
    split = predicted_exchange_split(
        grid, block_hw, radius, fuse, backend=backend, storage=storage,
        shape=shape, tile=tile, quantize=quantize, separable=sep,
        platform=platform, device_kind=device_kind, overlap=overlap,
        col_mode=col_mode)
    frac = split["exchange_fraction"]
    hidden_of_ex = split["exchange_hidden_fraction"]
    wall, ex_s, comp_s, hid_s, *_ = _m()
    if wall_s is not None:
        wall.observe(wall_s, backend=backend)
        ex_s.inc(wall_s * frac, backend=backend)
        comp_s.inc(wall_s * (1.0 - frac), backend=backend)
        if split["exchange_hidden_of_total"] > 0.0:
            # Exchange time the pipeline rode under the compute share —
            # informational (it overlaps compute seconds, not additive).
            hid_s.inc(wall_s * split["exchange_hidden_of_total"],
                      backend=backend)
        if by["total"] > 0:
            # Per-slab wait attribution (round 16, partitioned
            # completion): the exposed and hidden exchange walls spread
            # across the four slab channels by byte share — the series
            # that says WHICH ghost direction a decomposition waits on.
            slab = metrics.counter(
                "pctpu_halo_slab_wait_seconds",
                "model-attributed exchange wall per halo slab channel, "
                "exposed vs hidden-under-compute",
                ("backend", "direction", "which"))
            exposed_s = wall_s * frac
            hidden_s = wall_s * split["exchange_hidden_of_total"]
            for d in DIRECTIONS:
                share = by[d] / by["total"]
                if share <= 0.0:
                    continue
                slab.inc(exposed_s * share, backend=backend, direction=d,
                         which="exposed")
                if hidden_s > 0.0:
                    slab.inc(hidden_s * share, backend=backend,
                             direction=d, which="hidden")
    record_halo(backend, by, by["rounds"], iters)
    if mg_level is not None:
        # Per-level multigrid attribution: sweeps executed at each grid
        # level, labeled so one series shows where cycle time goes.
        metrics.counter(
            "pctpu_mg_sweeps_total",
            "multigrid smoothing sweeps executed per grid level",
            ("backend", "pctpu_mg_level")).inc(
            iters, backend=backend, pctpu_mg_level=str(int(mg_level)))
    events.emit(
        "exchange", source=source, backend=backend,
        grid=f"{grid[0]}x{grid[1]}", block=list(block_hw),
        radius=int(radius), fuse=int(fuse), iters=int(iters),
        storage=storage, boundary=boundary, rounds=by["rounds"],
        halo_bytes={d: by[d] for d in DIRECTIONS},
        exchange_fraction=round(frac, 4),
        overlap=bool(split["overlap"]),
        col_mode=str(col_mode),
        exchange_hidden_fraction=round(hidden_of_ex, 4),
        **({"mg_level": int(mg_level)} if mg_level is not None else {}),
        **({"wall_s": round(wall_s, 6)} if wall_s is not None else {}))
    # Trace attribution (round 13): when this step runs under an active
    # span (the serving device span, a traced converge call), split the
    # measured wall into model-attributed exchange / compute CHILD spans
    # — the reference's per-phase MPI_Wtime breakdown made first-class.
    # The exchange span's dur is the EXPOSED share; the hidden-under-
    # compute share (r12 overlap) rides as an attribute because it
    # overlaps the compute span's interval rather than adding to it.
    ctx = trace.current()
    if ctx is not None and wall_s is not None and wall_s > 0:
        ex_s = wall_s * frac
        comp_s = wall_s - ex_s
        t0 = time.time() - wall_s
        trace.emit_span(
            "exchange", trace_id=ctx.trace_id, parent_id=ctx.span_id,
            start_ts=t0, dur_s=ex_s, backend=backend, source=source,
            overlap=bool(split["overlap"]),
            hidden_s=round(wall_s * split["exchange_hidden_of_total"], 6),
            halo_bytes=by["total"], rounds=by["rounds"])
        trace.emit_span(
            "compute", trace_id=ctx.trace_id, parent_id=ctx.span_id,
            start_ts=t0 + ex_s, dur_s=comp_s, backend=backend,
            source=source, iters=int(iters))
    return {"halo_bytes": by, "exchange_fraction": frac,
            "exchange_hidden_fraction": hidden_of_ex,
            "overlap": bool(split["overlap"])}


def record_halo(backend: str, halo_bytes: dict, rounds: int,
                iters: int, wire_bytes: int | None = None) -> None:
    """The byte, round and iteration counters of one compiled-iterate
    call; ``halo_bytes`` maps each of :data:`DIRECTIONS` to bytes, and
    ``wire_bytes`` is what a remote-DMA kernel's copies moved (None for
    forms that exchange otherwise)."""
    *_, hbytes, rounds_c, iters_c = _m()
    for d in DIRECTIONS:
        hbytes.inc(halo_bytes[d], backend=backend, direction=d)
    rounds_c.inc(rounds, backend=backend)
    iters_c.inc(iters, backend=backend)
    if wire_bytes is not None:
        metrics.counter(
            "pctpu_rdma_wire_bytes_total",
            "bytes the remote-DMA kernels' band copies moved over the mesh",
            ("backend",)).inc(wire_bytes, backend=backend)


def record_drift(plan_key: str, backend: str, predicted_gpx: float | None,
                 measured_gpx: float | None) -> None:
    """The predicted-vs-measured Gpx/s/chip drift series per plan key —
    ROADMAP 5a's recalibration input, fed by BOTH the serving engine and
    ``bench_iterate`` through this one helper so the series can never
    desynchronize between producers."""
    if (not metrics.enabled() or not predicted_gpx
            or measured_gpx is None or measured_gpx <= 0):
        return
    g = metrics.gauge(
        "pctpu_plan_gpx_per_chip",
        "per-plan-key Gpx/s/chip, predicted vs measured",
        ("key", "backend", "which"))
    g.set(round(predicted_gpx, 6), key=plan_key, backend=backend,
          which="predicted")
    g.set(round(measured_gpx, 6), key=plan_key, backend=backend,
          which="measured")
    metrics.gauge(
        "pctpu_plan_drift_ratio",
        "measured/predicted Gpx/s per plan key (1.0 = calibrated)",
        ("key", "backend")).set(
        round(measured_gpx / predicted_gpx, 6), key=plan_key,
        backend=backend)
