"""Benchmark module (reference component C10, SURVEY.md §2 and §6).

The reference wraps its hot loop in ``MPI_Wtime`` and reduces the max
elapsed across ranks; numbers land in hand-made README tables.  Here the
walls are ``jax.block_until_ready`` fences around compiled runners and the
output is structured rows (dict/JSON) feeding the driver's ``bench.py``:

* **Gpixels/sec/chip** — pixels iterated per second per device
  (``H*W*iters / wall / n_devices``), the BASELINE.json headline metric.
* **halo-exchange p50 latency** — median wall of one compiled halo-pad
  round trip over the mesh, the latency-bound tail the reference measures
  implicitly at small block sizes.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.parallel import halo, step as step_lib
from parallel_convolution_tpu.parallel.mesh import (
    AXES, block_sharding, grid_shape, make_grid_mesh,
)
from jax import shard_map
from parallel_convolution_tpu.utils.platform import topology


def fence(x):
    """Block until everything ``x`` depends on has finished; returns ``x``.

    On an attached device ``jax.block_until_ready`` waits for the
    device, so a host clock read after it times the work, not the
    enqueue.
    """
    jax.block_until_ready([l for l in jax.tree.leaves(x)
                           if hasattr(l, "ndim")])
    return x


def wall(fn, *args, warmup: int = 1, reps: int = 3) -> float:
    """Median wall-clock seconds of ``fn(*args)`` fully materialized."""
    for _ in range(warmup):
        fence(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fence(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def bench_iterate(
    shape: tuple[int, int],
    filt: Filter,
    iters: int,
    mesh=None,
    channels: int = 1,
    backend: str = "shifted",
    quantize: bool = True,
    storage: str = "f32",
    fuse: int | None = 1,
    boundary: str = "zero",
    reps: int = 3,
    tile: tuple[int, int] | None = None,
    fallback: bool = False,
    overlap: bool | None = None,
    col_mode: str | None = None,
) -> dict:
    """Gpixels/sec/chip for the standard fixed-iteration workload.

    ``tile`` overrides the Pallas output-tile shape (None = per-kernel
    default) — passed explicitly because it is a static jit argument;
    monkeypatching the module defaults does NOT reach already-traced
    kernels.

    Every row is stamped with ``platform`` and ``device_kind`` (the mesh
    devices' — a CPU row can never read as a chip record) and
    ``effective_backend``.  ``fallback=True``
    additionally walks the degradation chain (resilience.degrade) on a
    transient compile/launch failure, and the row then records the
    backend that ACTUALLY produced the number, with the requested one
    still under ``backend``.

    ``backend="auto"`` (optionally with ``fuse=None``/``tile=None``)
    resolves through the tuning subsystem BEFORE the degrade walk; the
    row's ``plan_source`` records where the plan came from
    (measured|interpolated|predicted — 'explicit' for named configs),
    and ``fuse``/``tile`` always record the values the executable was
    ACTUALLY compiled with (post-resolution, post-clamp), never the
    caller-passed ones — an evidence row can no longer disagree with
    the program it timed.  ``predicted_gpx_per_chip`` is the cost
    model's figure for the same config, so a silent mistune shows as a
    measured-vs-predicted gap in every row."""
    if mesh is None:
        mesh = make_grid_mesh()
    reps = max(1, reps)
    H, W = shape
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(channels, H, W)).astype(np.float32)

    # The knobs the executable will ACTUALLY be compiled with
    # (step.resolve_launch: auto-resolution, clamps, the degrade walk on
    # the real block and storage) — stamped below, so a row can never
    # disagree with the compiled program.
    launch = step_lib.resolve_launch(
        mesh, filt, x.shape, backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        overlap=overlap, col_mode=col_mode, fallback=fallback)
    effective, fuse, tile = launch.backend, launch.fuse, launch.tile
    overlap, col_mode = launch.overlap, launch.col_mode
    plan_source = launch.plan_source
    # Time ONLY the on-device iteration: host->device transfer happens
    # once, and because the runner donates its input, repetitions chain
    # output->input — padded shape, dtype and sharding are invariant,
    # exactly the double-buffer reuse the real pipeline gets.
    xs, valid_hw, block_hw = step_lib._prepare(x, mesh, launch)
    fn = step_lib._iterate_runner(mesh, filt, iters, quantize, valid_hw,
                                  launch)
    out = fence(fn(xs))  # compile + warmup
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fence(fn(out))
        walls.append(time.perf_counter() - t0)
    secs = statistics.median(walls)
    n_dev = mesh.size
    gpx = H * W * channels * iters / secs / 1e9
    dev0 = mesh.devices.flat[0]
    # Stamp what was COMPILED, not what was passed: the same clamp
    # _build_iterate applies, and the kernel tile the launch actually
    # used (explicit/auto-resolved value, else the per-kernel module
    # default for Pallas tiers; None for backends with no tile).
    from parallel_convolution_tpu.tuning import costmodel, search
    from parallel_convolution_tpu.tuning.plans import Workload

    compiled_fuse = max(1, min(fuse, iters or 1))
    compiled_tile = costmodel.effective_tile(effective, tile)
    if effective == "pallas_rdma" and not costmodel.rdma_is_tiled(
            (channels, H, W), block_hw, filt.radius, compiled_fuse, storage,
            col_mode=col_mode, grid=grid_shape(mesh)):
        compiled_tile = None  # monolithic kernel: no output tile exists
    w = Workload.from_mesh(mesh, filt, (channels, H, W), storage=storage,
                           quantize=quantize, boundary=boundary)
    predicted = costmodel.predict_gpx_per_chip(search.predict(
        w, search.Candidate(effective, compiled_fuse, compiled_tile,
                            overlap, col_mode)))
    # Exchange/overlap attribution (obs.attribution): the analytic
    # per-direction ghost-band bytes of this decomposition and the
    # roofline model's exchange share — the per-phase instrumentation
    # the overlapped-halo and topology roadmap items are judged against.
    grid = grid_shape(mesh)
    from parallel_convolution_tpu.obs import attribution

    # record_step feeds the metric series AND returns the attribution
    # this row stamps; with obs disabled it returns None and the row's
    # analytic fields are computed directly (pure math, always on).
    att = attribution.record_step(
        backend=effective, grid=grid, block_hw=block_hw,
        radius=filt.radius, fuse=compiled_fuse, iters=iters,
        channels=channels, storage=storage, boundary=boundary,
        wall_s=secs, shape=(channels, H, W), quantize=quantize,
        tile=compiled_tile, platform=dev0.platform,
        device_kind=getattr(dev0, "device_kind", "") or "",
        source="bench", overlap=overlap, col_mode=col_mode)
    if att is None:
        split = attribution.predicted_exchange_split(
            grid, block_hw, filt.radius, compiled_fuse,
            backend=effective, storage=storage,
            shape=(channels, H, W), tile=compiled_tile,
            quantize=quantize,
            separable=effective in ("separable", "pallas_sep"),
            platform=dev0.platform,
            device_kind=getattr(dev0, "device_kind", "") or "",
            overlap=overlap)
        att = {
            "halo_bytes": attribution.halo_bytes_total(
                grid, block_hw, filt.radius, compiled_fuse, iters,
                channels, storage, boundary),
            "exchange_fraction": split["exchange_fraction"],
            "exchange_hidden_fraction": split["exchange_hidden_fraction"],
        }
    # The drift series (ROADMAP 5a's recalibration input): the bench
    # measurement against the model's figure, per plan key.
    attribution.record_drift(w.key(), effective, predicted, gpx / n_dev)
    return {
        "workload": f"{filt.name} {H}x{W}x{channels} {iters} iters",
        "backend": backend,
        # The backend that ACTUALLY produced this number (differs from
        # 'backend' only when fallback degraded it, or when 'auto' was
        # resolved) and the hardware it ran on — a silent CPU fallback
        # or tier downgrade can no longer masquerade as the requested
        # configuration in published rows.
        "effective_backend": effective,
        "platform": dev0.platform,
        "device_kind": getattr(dev0, "device_kind", "") or "",
        "storage": storage,
        "fuse": compiled_fuse,
        "tile": (f"{compiled_tile[0]}x{compiled_tile[1]}"
                 if compiled_tile else None),
        # The RESOLVED overlap knob (post-auto-resolution, post-clamp,
        # post-degrade) — the program this row timed either was or was
        # not the interior-first pipeline; the row says which.
        "overlap": bool(overlap),
        # The RESOLVED column-slab transport, same stamping rule
        # ("packed" is the canonical inert label off the RDMA tier).
        "col_mode": col_mode,
        "plan_source": plan_source,
        # The canonical tuning identity of the timed config — the
        # drift-series label and perf_gate.py's history key.
        "plan_key": w.key(),
        "predicted_gpx_per_chip": round(predicted, 3),
        "mesh": "x".join(str(s) for s in grid),
        "devices": n_dev,
        # Topology identity (ROADMAP item 1's keying, pulled forward in
        # r17): perf_gate.row_key keys multi-host rows separately so
        # they are never judged against single-host baselines.
        **topology(mesh),
        "wall_s": round(secs, 4),
        "gpixels_per_s": round(gpx, 3),
        "gpixels_per_s_per_chip": round(gpx / n_dev, 3),
        # Exchange attribution: the model's exchange share of one
        # iteration and the analytic ghost-band bytes this run moved
        # (whole mesh, all rounds, per direction) — obs.attribution.
        "exchange_fraction": round(att["exchange_fraction"], 4),
        # Overlap-adjusted split: the share of exchange time the
        # interior-first pipeline hides under compute (0.0 serialized).
        "exchange_hidden_fraction": round(
            att.get("exchange_hidden_fraction", 0.0), 4),
        "halo_bytes": att["halo_bytes"],
    }


def bench_converge(
    shape: tuple[int, int],
    filt: Filter,
    tol: float,
    max_iters: int,
    mesh=None,
    channels: int = 1,
    backend: str = "shifted",
    storage: str = "f32",
    boundary: str = "zero",
    check_every: int = 10,
    fuse: int | None = 1,
    tile: tuple[int, int] | None = None,
    solver: str = "jacobi",
    mg_levels: int | None = None,
    overlap: bool | None = None,
    col_mode: str | None = None,
    seed: int = 0,
) -> dict:
    """One run-to-convergence row, solver-comparable by construction.

    The row's ``work_units_to_tol`` is the fine-grid work spent reaching
    ``tol`` — iterations for jacobi, the pixel-weighted per-level sum for
    multigrid — so a multigrid row and a jacobi row on the same problem
    divide into the convergence speedup directly.  ``solver`` and
    ``mg_levels`` are stamped POST-RESOLUTION like tile/fuse: the level
    count is what the planner actually scheduled (never the requested
    cap), and ``plan_key`` carries a ``solver=`` suffix for non-jacobi
    rows so ``scripts/perf_gate.py`` never judges a multigrid row
    against a jacobi baseline.
    """
    if mesh is None:
        mesh = make_grid_mesh()
    from parallel_convolution_tpu.tuning.plans import Workload

    H, W = shape
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((channels, H, W)).astype(np.float32)
    # Post-resolution stamping, same rule as bench_iterate: resolve
    # backend="auto"/fuse=None/tile=None FIRST so the row records the
    # program that actually ran.
    launch = step_lib.resolve_launch(
        mesh, filt, x.shape, backend=backend, storage=storage,
        quantize=False, boundary=boundary, fuse=fuse, tile=tile,
        overlap=overlap, col_mode=col_mode,
        check_every=int(check_every))
    backend, fuse, tile = launch.backend, launch.fuse, launch.tile
    overlap, col_mode = launch.overlap, launch.col_mode
    w = Workload.from_mesh(mesh, filt, (channels, H, W), storage=storage,
                           quantize=False, boundary=boundary)
    dev0 = mesh.devices.flat[0]
    grid = grid_shape(mesh)
    row = {
        "workload": f"converge {filt.name} {H}x{W}x{channels} tol={tol}",
        "backend": backend,
        "solver": solver,
        "storage": storage,
        "boundary": boundary,
        "platform": dev0.platform,
        "device_kind": getattr(dev0, "device_kind", "") or "",
        "mesh": "x".join(str(s) for s in grid),
        "devices": mesh.size,
        "tol": float(tol),
        # Topology identity — same r17 keying rule as bench_iterate.
        **topology(mesh),
    }
    t0 = time.perf_counter()
    if solver == "multigrid":
        from parallel_convolution_tpu.solvers import multigrid

        out, res = multigrid.mg_converge(
            x, filt, tol=tol, max_iters=max_iters, mesh=mesh,
            quantize=False, backend=backend, storage=storage,
            boundary=boundary, fuse=fuse, tile=tile, overlap=overlap,
            mg_levels=mg_levels, col_mode=col_mode)
        row.update({
            "effective_backend": res.backend,
            "overlap": res.overlap,
            "col_mode": res.col_mode,
            "converged": res.converged,
            "residual": float(res.residual),
            "cycles": res.cycles,
            # Post-resolution stamps: what the planner actually
            # scheduled, not the requested cap.
            "mg_levels": res.levels,
            "mg_level_shapes": res.level_shapes,
            "work_units_to_tol": res.work_units,
            "predicted_s_per_cycle": res.predicted_s_per_cycle,
            # The solver is part of the history identity: a V-cycle's
            # work trajectory must never be judged against sweep counts.
            "plan_key": f"{w.key()}|solver=multigrid",
        })
    else:
        # The host-driven stream (byte-identical final image to
        # sharded_converge, same chunk math) reads the diff back per
        # chunk, so convergence is judged on diff < tol itself — the
        # iters < max_iters proxy misreports a run that reaches tol
        # exactly on the final permitted chunk.
        out, iters, diff = x, 0, None
        for out, iters, diff in step_lib.sharded_converge_stream(
                x, filt, tol=tol, max_iters=max_iters,
                check_every=check_every, mesh=mesh, quantize=False,
                backend=backend, storage=storage, boundary=boundary,
                fuse=fuse, tile=tile, overlap=overlap,
                col_mode=col_mode):
            pass
        row.update({
            "effective_backend": backend,
            "col_mode": col_mode,
            "converged": diff is not None and diff < tol,
            "residual": diff,
            "iters": iters,
            "mg_levels": None,
            "work_units_to_tol": float(iters),
            "plan_key": w.key(),
        })
    secs = max(time.perf_counter() - t0, 1e-9)
    row["wall_s"] = round(secs, 4)
    # Fine-grid pixel updates per second — the gateable throughput of a
    # convergence run (work-unit-weighted, so a V-cycle's coarse sweeps
    # are charged at their pixel ratio; perf_gate's history separates
    # solvers by key, this number tracks each solver's own trajectory).
    row["gpixels_per_s"] = round(
        row["work_units_to_tol"] * H * W * channels / secs / 1e9, 5)
    row["checksum"] = float(np.abs(np.asarray(out)).max())
    return row


def halo_bench_rounds(mesh, grid, r: int, n: int, exchange: bool):
    """The halo benchmark's chained round runner, at module scope so the
    HLO regression test (`test_bench_halo_rounds_keep_collectives`)
    compiles the SAME code `bench_halo_p50` times — not a private copy
    that could drift while the real round regresses to an elided graph.

    The exchange round carries forward the window STARTING at the ghost
    corner — it consumes the ppermuted ghosts and rotates the data
    across devices, which is what keeps the collective alive in the
    compiled loop (see `bench_halo_p50`'s definition note).  The control
    round moves the same bytes with a local roll and has no collective.
    """

    def body(v):
        def one(_, b):
            if exchange:
                p = halo.halo_exchange(b, r, grid)
                return p[:, : b.shape[1], : b.shape[2]]
            return jnp.roll(b, (r, r), axis=(1, 2))

        return jax.lax.fori_loop(0, n, one, v)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
    ))


def bench_halo_p50(
    block_shape: tuple[int, int],
    r: int = 1,
    mesh=None,
    trials: int = 20,
    chain_rounds: int | None = None,
) -> dict:
    """p50 amortized latency of one compiled halo exchange over the mesh.

    ``block_shape`` is the per-device block (the reference's per-rank tile);
    latency is what bounds small-block scaling (SURVEY.md §3.2).

    DEFINITION (round 5, one procedure for every consumer): each trial
    times ONE jitted span of ``chain_rounds`` chained LIVE exchange
    rounds and one span of equal-shape local-control rounds, and reports
    their difference divided by the count; p50/p90 are over trials.

    Two failure modes of earlier procedures, both caught this round:

    * A single fenced round (pre-round-5) is dominated by per-dispatch
      host noise — its p50 swung 1.4 → 16 ms, 10×, across
      identical driver runs.
    * Worse, a chained round built as ``slice(exchange(b))`` back to
      ``b``'s own window is the IDENTITY: XLA cancels slice-of-concat
      and emits ZERO collective-permutes (verified in HLO), so every
      earlier number — ms-scale and µs-scale alike — timed an
      empty graph.  The fuse-delta cross-check
      (``scripts/halo_cross_check.py``) exposed this: its derived
      saving was 44× the "measured" cost.

    The live round therefore consumes the ghosts: it carries forward the
    (bh, bw) window that STARTS at the ghost corner, so the data rotates
    across devices and neither slice-of-concat cancellation nor
    loop-invariant hoisting can elide the ppermutes (asserted in HLO by
    ``test_bench_halo_rounds_keep_collectives``).  The control round is
    a local ``jnp.roll`` by the same shift — same consumer bytes, no
    collective — so the differenced number isolates exchange cost (pad,
    two-phase ppermute, stitch) from the consumer copy.
    """
    if mesh is None:
        mesh = make_grid_mesh()
    grid = grid_shape(mesh)
    bh, bw = block_shape
    if mesh.size == 1:
        # On a 1×1 mesh halo_exchange._shift short-circuits to zeros_like —
        # there is NO collective, so any number "measured" here would be
        # the latency of nothing.  Refuse with an explicit sentinel rather
        # than record a vacuous 0.0 (round-1 BENCH did exactly that).
        return {
            "block": f"{bh}x{bw}", "radius": r,
            "mesh": "1x1", "p50_us": None, "p90_us": None,
            "unmeasurable": "1x1 mesh has no collective to time",
        }
    H, W = bh * grid[0], bw * grid[1]
    x = jax.device_put(
        np.random.default_rng(0).random((1, H, W)).astype(np.float32),
        block_sharding(mesh),
    )

    def rounds(n, exchange):
        return halo_bench_rounds(mesh, grid, r, n, exchange)

    k = chain_rounds or 256
    fnx, fnc = rounds(k, True), rounds(k, False)
    fence(fnx(x)), fence(fnc(x))  # compile
    times = []
    clamped = 0

    def span(fn):
        t0 = time.perf_counter()
        fence(fn(x))
        return time.perf_counter() - t0

    # Differenced amortized cost: per trial, one fenced span of k
    # live-exchange rounds minus one span of k local-control rounds, over
    # k.  Dispatch + fence cost cancels in the difference AND is amortized
    # (<1% at k=256); pairing the legs inside one trial also cancels slow
    # host-load drift.
    for _ in range(trials):
        d = (span(fnx) - span(fnc)) / k
        if d <= 0:
            clamped += 1  # noise swamped the exchange; never emit <0
            d = 0.0
        times.append(d)
    times.sort()
    p50 = 1e6 * times[len(times) // 2]
    p90 = 1e6 * times[int(len(times) * 0.9)]
    row = {
        "block": f"{bh}x{bw}", "radius": r,
        "mesh": "x".join(str(s) for s in grid),
        "p50_us": round(p50, 1),
        "p90_us": round(p90, 1),
        "trials": trials,
        "rounds_per_trial": k,
        "timing": f"amortized-diff-{k}",
    }
    if clamped:
        row["clamped_trials"] = clamped
    if p50 <= 0.0 and clamped:
        # The median itself sits on the clamp: the signal never rose above
        # the noise floor, so there is no measurement — null, flagged.
        # Same for a clamped p90: 0.0 µs is impossible, not a tail latency.
        row["p50_us"] = None
        row["noise_floor"] = True
        if p90 <= 0.0:
            row["p90_us"] = None
    return row


def bench_oracle_proxy(shape=(1920, 2520), iters: int = 2,
                       reps: int = 5) -> dict:
    """Serial CPU proxy (BASELINE config 1) via the NumPy oracle.

    The reference's own published numbers were unreadable (empty mount),
    so the honest single-process baseline is
    measured here, not copied.  Prefers the native C++ serial binary when
    built (a truer stand-in for the reference's C), else NumPy.

    This number is the denominator of every headline speedup claim, so it
    is the median of ``reps`` trials with the min→max spread recorded —
    a single 2-iteration trial swung ±20% between otherwise identical
    rounds (0.059–0.070 Gpx/s, BENCH_r01–r03) and dragged vs_baseline
    with it.
    """
    from parallel_convolution_tpu.ops import oracle
    from parallel_convolution_tpu.ops.filters import get_filter

    H, W = shape
    img = np.random.default_rng(0).integers(0, 256, size=(H, W)).astype(np.uint8)
    filt = get_filter("blur3")
    run = oracle.run_serial_u8
    impl = "numpy-oracle"
    try:
        from parallel_convolution_tpu.native import serial_native

        # Warm-up call outside the timed span so a first-use C++ build (or
        # page-in) doesn't pollute the measurement.
        serial_native.run_serial_u8(img[:8, :8], filt, 1)
        # threads=1: this row is the strict serial C1 baseline, not the
        # OpenMP hybrid tier (threads=0 default).
        run = lambda *a: serial_native.run_serial_u8(*a, threads=1)
        impl = "cpp-serial"
    except Exception:
        pass
    walls = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        run(img, filt, iters)
        walls.append(max(time.perf_counter() - t0, 1e-9))
    secs = statistics.median(walls)
    return {
        "workload": f"serial blur3 {H}x{W} {iters} iters",
        "impl": impl,
        "wall_s": round(secs, 4),
        "gpixels_per_s": float(f"{H * W * iters / secs / 1e9:.5g}"),
        "reps": len(walls),
        "spread_pct": round(100.0 * (max(walls) - min(walls)) / secs, 1),
    }
