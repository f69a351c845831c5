"""Geometric multigrid V-cycle on the sharded halo machinery.

Plain Jacobi smoothing kills high-frequency error fast and low-frequency
error at ``1 - O(1/N²)`` per sweep — the reference's run-to-convergence
config needs O(N²) sweeps.  A V-cycle makes every frequency band
high-frequency on SOME grid: pre-smooth → restrict the residual to a
half-resolution grid → recursively solve the error equation there →
prolong the correction back → post-smooth.  Work per cycle is a
geometric series (each level is 4× cheaper), so the whole cycle costs a
few fine-grid sweeps while contracting error at a rate independent of N
— the orders-of-magnitude convergence win ROADMAP item 4 names.

Everything rides the existing machinery rather than re-implementing it:

* **Smoothing is the iterate path.**  Fine-level pre-smoothing is
  ``step._build_iterate`` and the post-smooth + convergence diff is
  ``step._build_converge_chunk`` — the same per-backend compiled
  programs (any registered smoother form, Pallas/RDMA included; the
  fine smoother inherits overlap legality from the kernel registry's
  capability bit).  Coarse levels smooth the error equation
  ``e ← mask(S e) + r`` — the SAME registry-built step plus the
  restricted residual, compiled per level.
* **Transfer operators are registered stencil forms.**
  ``restrict_fw`` / ``prolong_bilinear`` (solvers.transfer) resolve
  through ``parallel.kernels`` exactly like a backend does and run
  inside ``shard_map`` on the level's mesh over depth-1 halo exchanges.
* **Coarse levels collapse onto sub-grids.**  When a level's per-device
  block falls below the tile floor (``MG_BLOCK_FLOOR``), the level
  planner walks the r10 shrink ladder (halve the larger mesh axis) and
  the level state moves via the round-10 reshard rule (crop to valid,
  re-pad, re-shard — ``step.reshard_prepared``'s in-memory math) — a
  64-device mesh does not ppermute 4×4 blocks at the bottom of the
  cycle.

The equation solved is the one the Jacobi path already iterates:
``u = mask(S u)`` (S = the filter stencil, mask = the zero ghost-ring /
pad-rim invariant), i.e. ``A u = 0`` with ``A = I - mask·S``.  The
convergence measure is UNCHANGED from ``sharded_converge``: the max-abs
change of one fine-grid sweep (= the residual norm of A, up to sign),
read back per cycle — so multigrid's stopping rule, its progressive
stream rows, and its oracle comparisons all speak the same unit as the
Jacobi solver, and correctness never depends on coarse-level exactness
(coarse sloppiness only costs cycles, the fine-grid residual is the
judge).

Work accounting: a **fine-grid work unit** is one fine-level sweep's
worth of pixel updates.  Each level-ℓ sweep costs ``pxℓ/px0`` units;
restriction+prolongation together are charged one sweep at their fine
level.  ``work_units_to_tol`` is the number every convergence row
stamps and the ``--mg-smoke`` gate compares (multigrid must reach tol
in ≥10× fewer units than plain Jacobi on the same problem).
"""

from __future__ import annotations

import dataclasses
import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from parallel_convolution_tpu.obs import metrics as obs_metrics
from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.parallel import kernels as kernel_forms
from parallel_convolution_tpu.parallel import step as step_lib
from parallel_convolution_tpu.parallel.mesh import (
    AXES, block_sharding, grid_shape, make_grid_mesh, padded_extent,
)
from parallel_convolution_tpu.resilience.faults import fault_point
from parallel_convolution_tpu.solvers.transfer import coarse_extent
from jax import shard_map

__all__ = ["MG_BLOCK_FLOOR", "MGResult", "Level", "cycle_work_units",
           "mg_converge", "mg_converge_stream", "plan_levels",
           "level_channel_keys", "warm_level_channels"]

# The tile floor: a level whose per-device block would dip below this on
# the inherited mesh collapses onto a smaller grid instead (sub-tile
# blocks are all rim — pure exchange latency, no compute to amortize it).
MG_BLOCK_FLOOR = 8
# Stop coarsening once the global extent is this small: the coarsest
# level is solved by smoothing alone, which is exact enough at 8x8.
MG_MIN_EXTENT = 8
MG_MAX_LEVELS = 12

# Default smoothing schedule: a V(2,2) cycle with a 16-sweep coarsest
# solve — the standard workhorse schedule (pre/post must stay small for
# the work-unit win; the coarsest grid is tiny so its sweeps are ~free).
NU_PRE = 2
NU_POST = 2
NU_COARSE = 16
# Damped-Jacobi relaxation weight, the standard 2D smoothing optimum.
# NOT optional: the undamped sweep leaves the checkerboard mode
# (eigenvalue −1) at full amplitude where full-weighting restriction
# cannot see it — measured as a dead stall at ~5e-2 residual on every
# grid depth ≥ 2 — while ω=4/5 contracts every high-frequency mode by
# ≥ 3/5 per sweep, which is what the coarse-grid correction needs.
OMEGA = 0.8


@dataclasses.dataclass(frozen=True)
class Level:
    """One grid level: its mesh, valid extent, and per-device block.

    ``block_hw * grid`` is the level's padded extent.  Every non-coarsest
    level has EVEN blocks (the planner pads to ``2*grid`` multiples) so
    restriction and prolongation stay device-local.
    """

    mesh: Mesh
    valid_hw: tuple[int, int]
    block_hw: tuple[int, int]

    @property
    def grid(self) -> tuple[int, int]:
        return grid_shape(self.mesh)

    @property
    def padded_hw(self) -> tuple[int, int]:
        g = self.grid
        return (self.block_hw[0] * g[0], self.block_hw[1] * g[1])


@dataclasses.dataclass
class MGResult:
    """The non-stream summary of one multigrid solve."""

    cycles: int
    work_units: float
    residual: float
    converged: bool
    levels: int
    level_grids: list[str]
    level_shapes: list[str]
    backend: str
    overlap: bool
    wall_s: float
    predicted_s_per_cycle: float | None = None
    col_mode: str = "strided"   # resolved column-slab transport of the
    #                            smoother programs (round 16)


def _level_block(valid_hw, grid, mult: int) -> tuple[int, int]:
    """Per-device block for ``valid_hw`` on ``grid`` padded to
    ``mult*grid`` multiples (mult=2 = the even-block rule)."""
    R, C = grid
    return (padded_extent(valid_hw[0], mult * R) // R,
            padded_extent(valid_hw[1], mult * C) // C)


def _fits(valid_hw, grid, mult: int, periodic: bool, floor: int) -> bool:
    bh, bw = _level_block(valid_hw, grid, mult)
    if periodic and (valid_hw[0] % (mult * grid[0])
                     or valid_hw[1] % (mult * grid[1])):
        # A torus level must keep valid == padded (halo wrap alignment).
        return False
    return min(bh, bw) >= floor


def _collapse(valid_hw, grid, mult: int, periodic: bool,
              floor: int) -> tuple[int, int] | None:
    """First rung of the shrink ladder (halve the larger axis — the r10
    ``grid_ladder`` walk) whose block clears the tile floor; None when
    even 1x1 cannot host the level (periodic misalignment)."""
    g = tuple(grid)
    while True:
        if _fits(valid_hw, g, mult, periodic, floor):
            return g
        if g == (1, 1):
            return None
        r, c = g
        g = (r, c // 2) if c >= r and c > 1 else (r // 2, c)


def plan_levels(mesh: Mesh, valid_hw, radius: int, boundary: str = "zero",
                mg_levels: int | None = None,
                floor: int = MG_BLOCK_FLOOR) -> list[Level]:
    """The level schedule: finest (the caller's mesh, as-is) down to the
    coarsest grid this problem/boundary supports.

    Rules, in order:

    * level 0 keeps the caller's mesh (the fine field lives there);
    * coarsening continues while ``mg_levels`` (when given) allows it,
      the global extent stays above ``MG_MIN_EXTENT``, and — for
      periodic boundaries — halving keeps torus alignment (even,
      grid-divisible extents);
    * every non-coarsest level pads its blocks EVEN (transfer locality);
    * a coarse level lands on the first shrink-ladder rung whose block
      clears ``floor`` (the coarse-grid reshard rule: state moves via
      crop-to-valid → re-pad → re-shard).
    """
    valid_hw = (int(valid_hw[0]), int(valid_hw[1]))
    periodic = boundary == "periodic"
    devices = list(mesh.devices.flat)
    cap = min(MG_MAX_LEVELS,
              mg_levels if mg_levels is not None else MG_MAX_LEVELS)
    if cap < 1:
        raise ValueError(f"mg_levels must be >= 1, got {mg_levels}")
    levels: list[Level] = []
    cur_valid, cur_grid = valid_hw, grid_shape(mesh)
    for idx in range(cap):
        more = (idx + 1 < cap and min(cur_valid) > MG_MIN_EXTENT
                and min(cur_valid) >= 2 * max(1, radius))
        if idx == 0:
            g = cur_grid  # the caller's mesh, never collapsed
            if more and periodic:
                # Even-block padding is always possible on the fine mesh
                # for zero boundaries; only a periodic misalignment
                # (torus levels must keep valid == padded) can veto
                # coarsening here.
                more = (cur_valid[0] % (2 * g[0]) == 0
                        and cur_valid[1] % (2 * g[1]) == 0)
        else:
            g = _collapse(cur_valid, cur_grid, 2 if more else 1,
                          periodic, floor)
            if more and g is None:
                more, g = False, _collapse(cur_valid, cur_grid, 1,
                                           periodic, floor)
            if g is None:
                break  # periodic level with no host at any rung: stop
        block = _level_block(cur_valid, g, 2 if more else 1)
        sub = (mesh if g == grid_shape(mesh)
               else make_grid_mesh(devices[: g[0] * g[1]], g))
        # Reuse the previous level's mesh object when the grid repeats,
        # so step/solver build caches key on ONE mesh per grid.
        if levels and levels[-1].grid == g:
            sub = levels[-1].mesh
        levels.append(Level(sub, cur_valid, block))
        if not more:
            break
        cur_valid = (coarse_extent(cur_valid[0], boundary),
                     coarse_extent(cur_valid[1], boundary))
        cur_grid = g
    return levels


def level_weights(levels) -> list[float]:
    """Fine-grid work units of ONE sweep at each level (pixel ratio)."""
    H0, W0 = levels[0].valid_hw
    return [(lv.valid_hw[0] * lv.valid_hw[1]) / float(H0 * W0)
            for lv in levels]


def cycle_work_units(levels, nu_pre: int = NU_PRE, nu_post: int = NU_POST,
                     nu_coarse: int = NU_COARSE) -> float:
    """Fine-grid work units of one V-cycle under the documented charge:
    every level-ℓ sweep costs its pixel ratio, the residual application
    is one sweep, restriction+prolongation together one more."""
    w = level_weights(levels)
    if len(levels) == 1:
        return (nu_pre + nu_post) * w[0]
    total = 0.0
    for i, wi in enumerate(w):
        if i == len(levels) - 1:
            total += nu_coarse * wi
        else:
            total += (nu_pre + nu_post + 1 + 1) * wi
    return total


def _level_sweeps(levels, nu_pre, nu_post, nu_coarse) -> list[int]:
    """Stencil applications per level per cycle (the obs attribution)."""
    if len(levels) == 1:
        return [nu_pre + nu_post]
    return [(nu_coarse if i == len(levels) - 1 else nu_pre + nu_post + 1)
            for i in range(len(levels))]


def level_channel_keys(levels, radius: int, boundary: str,
                       col_mode: str, channels: int = 1,
                       storage: str = "f32"):
    """The per-level persistent-channel identities of one V-cycle
    schedule (round 16): each level's exchange identity
    ``(grid, block, radius, fuse=1, dtype, boundary, kernel_form,
    col_mode)``, computed ONCE on the schedule and warmed into the
    channel-plan cache — every cycle's smoother kernels then BIND the
    same cached plans, so ``channels.stats()['builds']`` equals the
    number of distinct level identities however many cycles run
    (asserted in tests/test_channels.py)."""
    from parallel_convolution_tpu.parallel import channels as chan
    from parallel_convolution_tpu.tuning import costmodel

    dtype = {"f32": "float32", "bf16": "bfloat16", "u8": "uint8"}[storage]
    keys = []
    for lv in levels:
        tiled = costmodel.rdma_is_tiled(
            (channels, *lv.padded_hw), lv.block_hw, int(radius), 1,
            storage, col_mode=col_mode, grid=lv.grid)
        keys.append(chan.ChannelKey(
            grid=lv.grid, block_hw=lv.block_hw, radius=int(radius),
            fuse=1, dtype=dtype, boundary=boundary,
            kernel="tiled" if tiled else "monolithic",
            col_mode=col_mode))
    return tuple(keys)


def warm_level_channels(levels, radius: int, boundary: str, col_mode: str,
                        channels: int = 1, storage: str = "f32"):
    """Bind every level's channel plan up front (idempotent — repeat
    calls hit the cache); returns the identity tuple."""
    from parallel_convolution_tpu.parallel import channels as chan

    keys = level_channel_keys(levels, radius, boundary, col_mode,
                              channels, storage)
    for k in keys:
        chan.plan_for(k)
    return keys


# -- compiled level programs (lru-cached like step's builders) -------------

_SPEC = P(None, *AXES)


@lru_cache(maxsize=128)
def _build_smooth_rhs(mesh: Mesh, filt: Filter, n: int, valid_hw, block_hw,
                      backend: str, boundary: str,
                      tile: tuple[int, int] | None,
                      col_mode: str = "strided"):
    """``n`` damped error-equation sweeps:
    ``e ← (1−ω)·e + ω·(mask(S e) + r)``.

    The step is the registry-built smoother form (the SAME program the
    iterate path compiles, fuse=1, float carry); the restricted residual
    ``r`` is masked, so the convex combination keeps the masking
    invariant.  ω is :data:`OMEGA` — see its definition note for why the
    undamped sweep cannot serve as a multigrid smoother.
    """
    fault_point("backend_compile")  # lru_cache miss == a fresh compile
    grid = grid_shape(mesh)
    step_lib._check_block_size(filt, block_hw)
    step_lib._note_compile("mg_smooth", backend, grid, n, 1, boundary,
                           block_hw)
    step = step_lib._make_block_step(
        filt, grid, valid_hw, block_hw, False, backend, 1, boundary, tile,
        step_lib._mesh_interpret(mesh), False, col_mode)

    def body(e, r):
        def sweep(_, v):
            return ((1.0 - OMEGA) * v + OMEGA * (step(v) + r)).astype(
                e.dtype)

        return lax.fori_loop(0, n, sweep, e)

    sharded = shard_map(body, mesh=mesh, in_specs=(_SPEC, _SPEC),
                        out_specs=_SPEC, check_vma=False)
    return jax.jit(sharded, donate_argnums=0)


@lru_cache(maxsize=128)
def _build_fine_smooth(mesh: Mesh, filt: Filter, n: int, valid_hw, block_hw,
                       backend: str, boundary: str,
                       tile: tuple[int, int] | None, overlap: bool,
                       with_diff: bool, col_mode: str = "strided"):
    """``n`` damped fine-grid sweeps of the homogeneous equation:
    ``u ← (1−ω)·u + ω·mask(S u)``.

    The step is the registry-resolved smoother form — the identical
    per-block program ``step._build_iterate`` compiles (fuse=1), RDMA
    overlap included when the fine level's resolved knob says so, so the
    fine smoother inherits every backend lever the iterate path has.

    ``with_diff=True`` additionally returns the max-abs UNDAMPED sweep
    change ``max|S u − u|`` observed at the last sweep — exactly the
    convergence measure ``sharded_converge`` stops on (for undamped
    Jacobi the sweep change IS that residual), so multigrid's stopping
    rule, its stream rows, and its oracle comparisons all speak the same
    unit as the plain solver.  Computed from the last sweep's own
    stencil application: the measure costs nothing extra.
    """
    fault_point("backend_compile")
    grid = grid_shape(mesh)
    step_lib._check_block_size(filt, block_hw)
    step_lib._note_compile("mg_fine", backend, grid, n, 1, boundary,
                           block_hw)
    step = step_lib._make_block_step(
        filt, grid, valid_hw, block_hw, False, backend, 1, boundary, tile,
        step_lib._mesh_interpret(mesh), overlap, col_mode)

    def damped(v, s):
        return ((1.0 - OMEGA) * v + OMEGA * s).astype(v.dtype)

    if with_diff:
        def body(u):
            u = lax.fori_loop(0, max(0, n - 1),
                              lambda _, v: damped(v, step(v)), u)
            s = step(u)
            delta = jnp.abs(s.astype(jnp.float32) - u.astype(jnp.float32))
            diff = lax.pmax(jnp.max(delta), AXES)
            return damped(u, s), diff

        out_specs = (_SPEC, P())
    else:
        def body(u):
            return lax.fori_loop(0, n, lambda _, v: damped(v, step(v)), u)

        out_specs = _SPEC
    sharded = shard_map(body, mesh=mesh, in_specs=_SPEC,
                        out_specs=out_specs, check_vma=False)
    return jax.jit(sharded, donate_argnums=0)


@lru_cache(maxsize=128)
def _build_residual_restrict(mesh: Mesh, filt: Filter, valid_hw, block_hw,
                             backend: str, boundary: str,
                             tile: tuple[int, int] | None, fine: bool,
                             col_mode: str = "strided"):
    """Residual + full-weighting restriction in ONE compiled program.

    ``fine=True``  : ``u → 4·restrict(S u − u)``  (the homogeneous fine
    equation ``A u = 0``: rhs is zero).
    ``fine=False`` : ``(e, r) → 4·restrict(S e + r − e)`` (a coarse
    level's error equation ``A e = r``).

    The ×4 is the coarse-grid operator scaling: ``A = I − S`` is the
    UNDIVIDED second-order operator (``(h²/4)·Δ`` for the 5-point
    ``jacobi3``), so halving the resolution quadruples the coarse
    ``A_2h`` on smooth modes — the restricted residual must carry the
    same factor or every coarse correction lands 4× too weak and the
    cycle degenerates to barely-better-than-smoothing (measured: 3913
    cycles vs ~15 on a 96² seeded problem).

    The restriction operator resolves through the kernel-form registry
    (``restrict_fw``) — the transfer stencil is dispatched exactly like
    a backend.
    """
    fault_point("backend_compile")
    grid = grid_shape(mesh)
    step_lib._check_block_size(filt, block_hw)
    step_lib._note_compile("mg_restrict", backend, grid, 1, 1, boundary,
                           block_hw)
    step = step_lib._make_block_step(
        filt, grid, valid_hw, block_hw, False, backend, 1, boundary, tile,
        step_lib._mesh_interpret(mesh), False, col_mode)
    restrict = kernel_forms.resolve(2, "restrict_fw", boundary).build(
        grid, valid_hw, block_hw, boundary)

    if fine:
        def body(u):
            return 4.0 * restrict((step(u) - u).astype(jnp.float32))

        in_specs = _SPEC
    else:
        def body(e, r):
            return 4.0 * restrict((step(e) + r - e).astype(jnp.float32))

        in_specs = (_SPEC, _SPEC)
    sharded = shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=_SPEC, check_vma=False)
    return jax.jit(sharded)  # no donation: the caller still needs u/e


@lru_cache(maxsize=128)
def _build_prolong_correct(mesh: Mesh, valid_hw, block_hw, boundary: str):
    """``(u, e_c) → u + prolong(e_c)`` — bilinear prolongation (the
    registry's ``prolong_bilinear`` form) fused with the correction
    add on the FINE level's mesh."""
    fault_point("backend_compile")
    grid = grid_shape(mesh)
    step_lib._note_compile("mg_prolong", "prolong_bilinear", grid, 1, 1,
                           boundary, block_hw)
    prolong = kernel_forms.resolve(2, "prolong_bilinear", boundary).build(
        grid, valid_hw, block_hw, boundary)

    def body(u, ec):
        return (u + prolong(ec).astype(u.dtype)).astype(u.dtype)

    sharded = shard_map(body, mesh=mesh, in_specs=(_SPEC, _SPEC),
                        out_specs=_SPEC, check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1))


def _fit_to(xs, valid_hw, mesh: Mesh, block_hw, src_mesh: Mesh):
    """Move a level state onto ``(mesh, block_hw)`` — the coarse-grid
    reshard rule (r10 machinery): crop to the valid extent, re-pad to
    the target blocks, re-shard.  Identity (no copy) when the state is
    already there; otherwise one small host round-trip — coarse levels
    are tiny by construction."""
    H, W = (int(v) for v in valid_hw)
    R, C = grid_shape(mesh)
    target = (block_hw[0] * R, block_hw[1] * C)
    if src_mesh is mesh and (xs.shape[1], xs.shape[2]) == target:
        return xs
    x = np.asarray(xs)[:, :H, :W]
    if (target[0], target[1]) != (H, W):
        x = np.pad(x, ((0, 0), (0, target[0] - H), (0, target[1] - W)))
    return jax.device_put(x, block_sharding(mesh))


# -- the solver ------------------------------------------------------------


def _mg_obs(levels, sweeps, filt, backend: str, channels: int,
            boundary: str, overlap: bool, cycle_wall: float) -> None:
    """Per-cycle telemetry: one exchange/compute attribution per LEVEL
    (``pctpu_mg_level``-labeled sweep counter + the exchange event with
    the level stamped), plus the cycle-wall histogram."""
    if not obs_metrics.enabled():
        return
    from parallel_convolution_tpu.obs import attribution

    for i, (lv, n) in enumerate(zip(levels, sweeps)):
        dev0 = lv.mesh.devices.flat[0]
        attribution.record_step(
            backend=backend, grid=lv.grid, block_hw=lv.block_hw,
            radius=filt.radius, fuse=1, iters=n, channels=channels,
            storage="f32", boundary=boundary, wall_s=None,
            shape=(channels, *lv.padded_hw), quantize=False, tile=None,
            platform=dev0.platform,
            device_kind=getattr(dev0, "device_kind", "") or "",
            source="multigrid", overlap=overlap and i == 0,
            mg_level=i)
    obs_metrics.histogram(
        "pctpu_mg_cycle_seconds", "wall of one multigrid V-cycle",
        ("backend",)).observe(cycle_wall, backend=backend)


def _predict_cycle_seconds(levels, sweeps, filt, backend: str,
                           channels: int, quantize: bool,
                           tile) -> float | None:
    """Cost-model price of one V-cycle: the SUM of its per-level sweep
    costs (``costmodel.predict_vcycle_seconds``) — coarse levels are
    cheaper, never free, so ``backend="auto"`` comparisons against a
    single-level solver stay honest."""
    try:
        from parallel_convolution_tpu.tuning import costmodel

        terms = []
        for lv, n in zip(levels, sweeps):
            dev0 = lv.mesh.devices.flat[0]
            hw = costmodel.hardware_for(
                dev0.platform, getattr(dev0, "device_kind", "") or "")
            spp = costmodel.predict_seconds_per_px_iter(
                backend, "f32", 1, tile, (channels, *lv.valid_hw),
                lv.block_hw, lv.grid, filt.size,
                backend in ("separable", "pallas_sep"), quantize, hw)
            terms.append((spp, channels * lv.valid_hw[0] * lv.valid_hw[1],
                          n))
        return costmodel.predict_vcycle_seconds(terms)
    except Exception:  # noqa: BLE001 — pricing must never kill a solve
        return None


def mg_converge_stream(x, filt: Filter, tol: float, max_iters: int,
                       mesh: Mesh | None = None, quantize: bool = False,
                       backend: str = "shifted", storage: str = "f32",
                       boundary: str = "zero",
                       fuse: int | None = 1,
                       tile: tuple[int, int] | None = None,
                       fallback: bool = False,
                       overlap: bool | None = None,
                       mg_levels: int | None = None,
                       col_mode: str | None = None,
                       nu_pre: int = NU_PRE, nu_post: int = NU_POST,
                       nu_coarse: int = NU_COARSE):
    """Progressive multigrid solve: a generator over V-cycle snapshots.

    Yields ``(image_f32, cycles_done, residual, work_units)`` after every
    V-cycle — ``residual`` is the max-abs change of one fine-grid sweep,
    the SAME measure ``sharded_converge`` stops on, read back per cycle
    (the readback is the fence).  The stream ends when ``residual <
    tol`` or the fine-grid work-unit budget ``max_iters`` is exhausted.

    ``quantize`` must be False and ``storage`` f32: multigrid corrections
    are signed float fields — a u8 store-back would clamp the error
    equation to garbage (typed ValueError, the serving layer's
    ``invalid``).  ``fuse`` is accepted for signature parity and ignored
    (smoothing sweeps are fuse=1; the V-cycle itself is the
    exchange-amortization lever here).  ``backend`` names the smoother
    form (``auto`` resolves through the tuning subsystem); transfer
    operators always run their registered stencils.
    """
    if mesh is None:
        mesh = make_grid_mesh()
    x = np.asarray(x, np.float32)
    levels, launch = _mg_resolve(
        x, filt, mesh, quantize=quantize, backend=backend, storage=storage,
        boundary=boundary, tile=tile, fallback=fallback, overlap=overlap,
        mg_levels=mg_levels, col_mode=col_mode)
    yield from _mg_cycles(x, filt, tol, max_iters, levels, launch,
                          nu_pre, nu_post, nu_coarse)


def _mg_resolve(x, filt: Filter, mesh: Mesh, *, quantize, backend, storage,
                boundary, tile, fallback, overlap, mg_levels, col_mode):
    """The level schedule and the smoother's launch, resolved once
    (``step.resolve_launch``) on the fine level's block at fuse 1 — the
    sweeps are unfused."""
    if quantize:
        raise ValueError(
            "solver='multigrid' requires quantize=False: corrections are "
            "signed float fields (u8 store-back would clamp the error "
            "equation)")
    if storage != "f32":
        raise ValueError(
            f"solver='multigrid' requires storage='f32', got {storage!r} "
            "(residual/correction fields need full float carries)")
    levels = plan_levels(mesh, x.shape[1:], filt.radius, boundary, mg_levels)
    launch = step_lib.resolve_launch(
        mesh, filt, x.shape, backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=1, tile=tile,
        overlap=overlap, col_mode=col_mode, fallback=fallback,
        block_hw=levels[0].block_hw)
    return levels, launch


def _mg_cycles(x, filt: Filter, tol: float, max_iters: int, levels,
               launch, nu_pre: int, nu_post: int, nu_coarse: int):
    """The V-cycle loop of :func:`mg_converge_stream` on a resolved
    launch."""
    channels, H, W = x.shape
    valid_hw = (int(H), int(W))
    fine = levels[0]
    backend, tile, storage = launch.backend, launch.tile, launch.storage
    overlap, col_mode, boundary = (launch.overlap, launch.col_mode,
                                   launch.boundary)
    if kernel_forms.persistent_capable(backend):
        # Cache each level's exchange identity on the schedule up front:
        # every cycle's smoother kernels bind these SAME plans.
        warm_level_channels(levels, filt.radius, boundary, col_mode,
                            channels, storage)
    sweeps = _level_sweeps(levels, nu_pre, nu_post, nu_coarse)
    wu_cycle = cycle_work_units(levels, nu_pre, nu_post, nu_coarse)
    u = _fit_to(x, valid_hw, fine.mesh, fine.block_hw, src_mesh=None)

    def coarse_cycle(i: int, r):
        """Solve ``A e = r`` on level ``i`` (one recursive V leg)."""
        lv = levels[i]
        e = jnp.zeros_like(r)
        if i == len(levels) - 1:
            return _build_smooth_rhs(
                lv.mesh, filt, nu_coarse, lv.valid_hw, lv.block_hw,
                backend, boundary, tile, col_mode)(e, r)
        e = _build_smooth_rhs(lv.mesh, filt, nu_pre, lv.valid_hw,
                              lv.block_hw, backend, boundary, tile,
                              col_mode)(e, r)
        rc = _build_residual_restrict(
            lv.mesh, filt, lv.valid_hw, lv.block_hw, backend, boundary,
            tile, False, col_mode)(e, r)
        nxt = levels[i + 1]
        rc = _fit_to(rc, nxt.valid_hw, nxt.mesh, nxt.block_hw,
                     src_mesh=lv.mesh)
        ec = coarse_cycle(i + 1, rc)
        ec = _fit_to(ec, nxt.valid_hw, lv.mesh,
                     (lv.block_hw[0] // 2, lv.block_hw[1] // 2),
                     src_mesh=nxt.mesh)
        e = _build_prolong_correct(lv.mesh, lv.valid_hw, lv.block_hw,
                                   boundary)(e, ec)
        return _build_smooth_rhs(lv.mesh, filt, nu_post, lv.valid_hw,
                                 lv.block_hw, backend, boundary, tile,
                                 col_mode)(e, r)

    cycles, wu, diff = 0, 0.0, float("inf")
    max_wu = float(max_iters)
    while wu < max_wu and diff >= tol:
        t0 = time.perf_counter()
        if len(levels) == 1:
            # Degenerate single-level schedule: the cycle is pure damped
            # smoothing (plan_levels refused to coarsen — tiny image or
            # periodic misalignment).
            u, d = _build_fine_smooth(
                fine.mesh, filt, nu_pre + nu_post, fine.valid_hw,
                fine.block_hw, backend, boundary, tile, overlap, True,
                col_mode)(u)
        else:
            u = _build_fine_smooth(
                fine.mesh, filt, nu_pre, fine.valid_hw, fine.block_hw,
                backend, boundary, tile, overlap, False, col_mode)(u)
            rc = _build_residual_restrict(
                fine.mesh, filt, fine.valid_hw, fine.block_hw, backend,
                boundary, tile, True, col_mode)(u)
            nxt = levels[1]
            rc = _fit_to(rc, nxt.valid_hw, nxt.mesh, nxt.block_hw,
                         src_mesh=fine.mesh)
            ec = coarse_cycle(1, rc)
            ec = _fit_to(ec, nxt.valid_hw, fine.mesh,
                         (fine.block_hw[0] // 2, fine.block_hw[1] // 2),
                         src_mesh=nxt.mesh)
            u = _build_prolong_correct(
                fine.mesh, fine.valid_hw, fine.block_hw, boundary)(u, ec)
            # Post-smooth + the residual readout in one compiled program
            # — the last sweep's undamped change ``max|S u − u|`` is the
            # residual norm the stream reports and the stopping rule
            # reads (the same measure sharded_converge stops on).
            u, d = _build_fine_smooth(
                fine.mesh, filt, nu_post, fine.valid_hw, fine.block_hw,
                backend, boundary, tile, overlap, True, col_mode)(u)
        diff = float(d)   # the readback fences the cycle
        cycles += 1
        wu += wu_cycle
        _mg_obs(levels, sweeps, filt, backend, channels, boundary, overlap,
                time.perf_counter() - t0)
        yield (np.asarray(u[:, :H, :W].astype(jnp.float32)), cycles,
               diff, round(wu, 3))


def mg_converge(x, filt: Filter, tol: float, max_iters: int,
                mesh: Mesh | None = None, quantize: bool = False,
                backend: str = "shifted", storage: str = "f32",
                boundary: str = "zero", fuse: int | None = 1,
                tile: tuple[int, int] | None = None,
                fallback: bool = False, overlap: bool | None = None,
                mg_levels: int | None = None,
                col_mode: str | None = None,
                nu_pre: int = NU_PRE, nu_post: int = NU_POST,
                nu_coarse: int = NU_COARSE) -> tuple[np.ndarray, MGResult]:
    """Run the V-cycle to convergence; returns ``(field_f32, MGResult)``.

    ``max_iters`` bounds FINE-GRID WORK UNITS (the same budget a plain
    Jacobi run would spend as iterations), so the two solvers are
    comparable under one cap.
    """
    if mesh is None:
        mesh = make_grid_mesh()
    x = np.asarray(x, np.float32)
    levels, launch = _mg_resolve(
        x, filt, mesh, quantize=quantize, backend=backend, storage=storage,
        boundary=boundary, tile=tile, fallback=fallback, overlap=overlap,
        mg_levels=mg_levels, col_mode=col_mode)
    sweeps = _level_sweeps(levels, nu_pre, nu_post, nu_coarse)
    t0 = time.perf_counter()
    out, cycles, diff, wu = x, 0, float("inf"), 0.0
    for out, cycles, diff, wu in _mg_cycles(x, filt, tol, max_iters, levels,
                                            launch, nu_pre, nu_post,
                                            nu_coarse):
        pass
    # The stamps are the launch the cycles ran, so the result row can
    # never disagree with the program that produced it.
    res = MGResult(
        cycles=cycles, work_units=round(wu, 3), residual=diff,
        converged=diff < tol, levels=len(levels),
        level_grids=[f"{lv.grid[0]}x{lv.grid[1]}" for lv in levels],
        level_shapes=[f"{lv.valid_hw[0]}x{lv.valid_hw[1]}" for lv in levels],
        backend=launch.backend, overlap=launch.overlap,
        col_mode=launch.col_mode,
        wall_s=round(time.perf_counter() - t0, 4),
        predicted_s_per_cycle=_predict_cycle_seconds(
            levels, sweeps, filt, launch.backend, x.shape[0], False,
            launch.tile))
    return out, res
