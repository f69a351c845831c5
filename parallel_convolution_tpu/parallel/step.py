"""Sharded iteration driver: the reference's main loop, SPMD-compiled.

Reference components C6 + C8 (SURVEY.md §2) and the hot loop of §3.2:

    for t in loops:
        exchange halos (Isend/Irecv + Waitall)
        convolute(block)
        swap(src, dst)
        every N iters: local diff → MPI_Allreduce → maybe break

becomes one ``jax.jit``-compiled ``shard_map`` over the ('x','y') mesh whose
body runs the whole iteration loop on-device: ``lax.fori_loop`` (fixed
iteration count) or ``lax.while_loop`` (run-to-convergence, the
``MPI_Allreduce`` becoming ``lax.pmax`` of the per-block max-abs diff).
The functional loop carry is the double buffer; donated input storage gives
XLA the reference's pointer swap for free.

Non-divisible images (e.g. 2520 over a 4-high grid) are padded to the next
block multiple — and, for a kernel form that moves whole (sublane, 128)
tiles, to blocks of whole tiles — and re-masked to zero every iteration,
which keeps the pad region behaving exactly like the oracle's zero ghost
ring: outputs stay bit-identical to the serial oracle for any mesh shape.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
import warnings
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from parallel_convolution_tpu.obs import (
    events as obs_events, metrics as obs_metrics, trace as obs_trace,
)
from parallel_convolution_tpu.ops import conv
from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.parallel import halo, kernels as kernel_forms
from parallel_convolution_tpu.parallel.mesh import (
    AXES,
    block_sharding,
    grid_shape,
    make_grid_mesh,
    padded_extent,
    replicated,
)
from parallel_convolution_tpu.resilience.faults import fault_point
from parallel_convolution_tpu.utils.config import (  # canonical registries
    AUTO, BACKENDS,
)
from jax import shard_map

__all__ = ["BACKENDS", "STORAGE_DTYPES", "Launch", "block_extent",
           "resolve_launch", "sharded_iterate", "sharded_converge",
           "sharded_converge_stream", "iterate_prepared",
           "reshard_prepared",
           "resolve_overlap", "resolve_col_mode", "clamp_col_mode",
           "iterate_text", "op_scopes"]


def _note_compile(builder: str, backend: str, grid, iters: int, fuse: int,
                  boundary: str, block_hw) -> None:
    """Telemetry for one fresh trace/compile (a build-cache miss): the
    ``compile`` event + a labeled counter.  One branch when obs is off.

    When the compile happens under an active trace (a cold serving key:
    the engine's compile span is current on this thread), the event
    carries the trace/span ids — ``trace_report.py`` can then show which
    request's span tree triggered which build-cache miss."""
    if not obs_metrics.enabled():
        return
    obs_metrics.counter(
        "pctpu_compiles_total", "fresh traces/compiles of iteration runners",
        ("builder", "backend")).inc(builder=builder, backend=backend)
    ctx = obs_trace.current()
    obs_events.emit(
        "compile", builder=builder, backend=backend,
        grid=f"{grid[0]}x{grid[1]}", iters=int(iters), fuse=int(fuse),
        boundary=boundary, block=[int(b) for b in block_hw],
        **({"trace_id": ctx.trace_id, "span_id": ctx.span_id}
           if ctx is not None else {}))


def _record_step_obs(backend: str, mesh: Mesh, block_hw, radius: int,
                     fuse: int, iters: int, channels: int, storage: str,
                     boundary: str, wall_s: float | None, shape,
                     quantize: bool, tile, source: str,
                     overlap: bool = False,
                     col_mode: str = "packed") -> None:
    from parallel_convolution_tpu.obs import attribution

    grid = grid_shape(mesh)
    dev0 = mesh.devices.flat[0]
    attribution.record_step(
        backend=backend, grid=grid, block_hw=block_hw, radius=radius,
        fuse=fuse, iters=iters, channels=channels, storage=storage,
        boundary=boundary, wall_s=wall_s, shape=shape, quantize=quantize,
        tile=tile, platform=dev0.platform,
        device_kind=getattr(dev0, "device_kind", "") or "", source=source,
        overlap=overlap, col_mode=col_mode)


def _valid_mask(valid_hw, block_hw, margin: int = 0):
    """Per-block validity mask of globally-in-image pixels, as (1, h, w) f32.

    ``margin`` extends the block by m on every side (the temporal-fusion
    intermediate levels live on such extended blocks); positions outside
    the valid global image — beyond the image edge *or* in the
    pad-to-multiple rim — are 0.
    """
    H, W = valid_hw
    bh, bw = block_hw
    m = margin
    row0 = lax.axis_index("x") * bh - m
    col0 = lax.axis_index("y") * bw - m
    shape = (bh + 2 * m, bw + 2 * m)
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = (rows >= 0) & (rows < H) & (cols >= 0) & (cols < W)
    return ok[None].astype(jnp.float32)


# Overlap resolution warn-once registry (one line per cause per process;
# the stamped knob, not stderr, is the durable record).
_OVERLAP_WARNED: set = set()

# Env escape hatch: run the overlapped program under interpreted Pallas
# anyway.  The CPU shim has no real async semaphore timing, so overlap
# buys nothing there and is force-serialized by default — but CI byte
# proofs (scripts/rdma_fuse_ab.py --overlap, the --overlap-smoke leg)
# must drive the overlapped PROGRAM through the full dispatch stack.
# Canonical name lives in the jax-free config registry; re-exported here
# because dispatch call sites (and tests) historically read it off step.
from parallel_convolution_tpu.utils.config import (  # noqa: E402
    OVERLAP_INTERPRET_ENV,
)


def _warn_overlap_once(cause: str, msg: str) -> None:
    if cause in _OVERLAP_WARNED:
        return
    _OVERLAP_WARNED.add(cause)
    warnings.warn(msg, UserWarning, stacklevel=3)


def resolve_overlap(overlap: bool | None, backend: str, mesh: Mesh) -> bool:
    """The overlap knob a launch will ACTUALLY compile with.

    ``None`` (the explicit-backend default) resolves to False — the
    serialized order; ``backend="auto"`` callers get a concrete bool
    from the tuning resolver before reaching here.  ``True`` is a
    clamped request, mirrored by ``tuning.resolve``:

    * only the RDMA kernels have an overlapped pipeline — any other
      backend force-serializes with a one-time warning;
    * interpreted Pallas (a CPU mesh) force-serializes with a one-time
      warning: the interpreter's DMAs have no real async timing, so the
      pipeline proves nothing and costs trace complexity — UNLESS
      ``PCTPU_OVERLAP_INTERPRET=1``, the CI byte-proof escape hatch
      (the A/B harness and the --overlap-smoke leg run the overlapped
      program through the whole dispatch stack to prove byte equality).

    Every bench row / serving response stamps the RESOLVED value, so a
    clamp is visible in artifacts, never only on stderr.
    """
    if overlap is None or not overlap:
        return False
    if not kernel_forms.overlap_capable(backend):
        # The per-form capability bit (kernel registry): only forms that
        # REGISTER an overlapped pipeline may keep the request.
        _warn_overlap_once(
            f"backend:{backend}",
            f"overlap=True requested but backend {backend!r} has no "
            "overlapped halo pipeline (RDMA kernels only); running "
            "serialized — rows stamp overlap=False")
        return False
    if _mesh_interpret(mesh) and not os.environ.get(OVERLAP_INTERPRET_ENV):
        _warn_overlap_once(
            "interpret",
            "overlap=True force-serialized under interpreted Pallas (the "
            "CPU shim has no real async semaphore timing; set "
            f"{OVERLAP_INTERPRET_ENV}=1 to run the overlapped program "
            "anyway for byte proofs) — rows stamp overlap=False")
        return False
    return True


def resolve_col_mode(col_mode, backend: str, mesh: Mesh, block_hw,
                     radius: int, fuse: int, storage: str) -> str:
    """The column-slab transport a launch will ACTUALLY compile with.

    ``None``/``"auto"`` resolve through the cost model
    (``costmodel.pick_col_mode`` — the derived-datatypes decision:
    strided descriptor overhead vs packed staging bytes) for
    persistent-capable forms; every other form has no in-kernel column
    RDMA transport, so the knob is inert there and normalizes to the
    canonical ``"packed"`` label (one value → one EngineKey / bench
    identity, matching the legacy-plan-record default).  An explicit
    packed/strided request on a capable form is honored verbatim — the
    two transports are byte-identical by construction, so no clamp
    warning is needed.  Every bench row / serving response stamps the
    RESOLVED value.
    """
    from parallel_convolution_tpu.parallel import channels

    if col_mode is not None and col_mode not in channels.COL_MODE_CHOICES:
        raise ValueError(
            f"col_mode must be one of {channels.COL_MODE_CHOICES}, got "
            f"{col_mode!r}")
    if not kernel_forms.persistent_capable(backend):
        return "packed"
    if grid_shape(mesh)[1] <= 1:
        # No remote column axis: both transports compile the identical
        # statically-elided program, so even an explicit request
        # normalizes to the canonical label — one program, one
        # EngineKey / bench identity (the same rule the tuner's
        # _legal_col_modes applies).
        return "packed"
    if col_mode in (None, "auto"):
        from parallel_convolution_tpu.tuning import costmodel

        dev0 = mesh.devices.flat[0]
        hw = costmodel.hardware_for(
            dev0.platform, getattr(dev0, "device_kind", "") or "")
        return costmodel.pick_col_mode(
            grid_shape(mesh), tuple(int(b) for b in block_hw), int(radius),
            max(1, int(fuse)), storage, hw)
    return col_mode


def clamp_col_mode(col_mode: str, backend: str) -> str:
    """Re-clamp a resolved col_mode after a degrade walk: a backend with
    no persistent channels normalizes to the canonical 'packed'."""
    return (col_mode if kernel_forms.persistent_capable(backend)
            else "packed")


def _boundary_geometry(grid, valid_hw, block_hw, boundary: str):
    """Shared geometry checks of every rank-2 form: periodic divisibility
    and whether the pad-to-multiple rim needs re-masking."""
    periodic = boundary == "periodic"
    if periodic and (valid_hw[0] != block_hw[0] * grid[0]
                     or valid_hw[1] != block_hw[1] * grid[1]):
        raise ValueError(
            "periodic boundary requires dimensions divisible by the mesh "
            f"grid: image {valid_hw} on grid {grid}"
        )
    needs_mask = not periodic and (valid_hw[0] != block_hw[0] * grid[0]
                                   or valid_hw[1] != block_hw[1] * grid[1])
    return periodic, needs_mask


def _build_rdma_step(filt: Filter, grid, valid_hw, block_hw, quantize: bool,
                     fuse: int = 1, boundary: str = "zero",
                     tile: tuple[int, int] | None = None,
                     interpret: bool | None = None,
                     overlap: bool = False,
                     col_mode: str = "strided"):
    """The ``pallas_rdma`` kernel form: exchange + stencil fused in ONE
    kernel (remote DMA over ICI instead of collective-permute +
    concatenate + re-read).  fuse=T>1 widens the in-kernel exchange to
    T*r-deep ghosts and runs T levels before returning — the kernel
    re-zeroes out-of-image positions per level against valid_hw, so the
    outer mask is only needed on the single-level path.  The only form
    registered ``overlap_capable`` (the interior-first pipeline) and
    ``persistent_capable`` (bound halo channels + the packed/strided
    ``col_mode`` column-transport A/B — resolved by the caller, never
    'auto' here)."""
    periodic, needs_mask = _boundary_geometry(grid, valid_hw, block_hw,
                                              boundary)

    def launch(shape, dtype):
        from parallel_convolution_tpu.ops import pallas_rdma

        return pallas_rdma.launch_plan(shape, dtype, dtype, filt.radius,
                                       fuse, grid, boundary, col_mode,
                                       tile=tile)

    def step(v):
        from parallel_convolution_tpu.ops import pallas_rdma

        halo.count_remote_exchange(v, filt.radius * fuse, grid, boundary)
        halo.count_wire(launch(v.shape, v.dtype).wire_bytes)
        p = pallas_rdma.fused_rdma_step(
            v, filt, grid, boundary, quantize=quantize,
            out_dtype=v.dtype, tile=tile, interpret=interpret,
            fuse=fuse, valid_hw=None if periodic else tuple(valid_hw),
            overlap=overlap, col_mode=col_mode,
        )
        if needs_mask and fuse == 1:
            p = p * _valid_mask(valid_hw, block_hw).astype(p.dtype)
        return p

    @lru_cache(maxsize=None)
    def launch_args(planes: int, dtype) -> dict:
        """What the build span says of this launch (one entry per block)."""
        return {"rdma_kernel": launch((planes, *block_hw), dtype).kernel,
                "col_mode": col_mode, "overlap": int(bool(overlap)),
                "block": [int(b) for b in block_hw]}

    step.launch_args = launch_args
    return step


def _build_halo_step(backend: str, filt: Filter, grid, valid_hw, block_hw,
                     quantize: bool, fuse: int = 1, boundary: str = "zero",
                     tile: tuple[int, int] | None = None,
                     interpret: bool | None = None,
                     overlap: bool = False,
                     col_mode: str = "strided"):
    """The halo-exchange kernel forms (every backend but ``pallas_rdma``):
    ``fuse`` iterations on a local block per collective halo exchange.

    fuse=1 is the reference's loop shape: exchange 1-deep halos, stencil,
    [quantize], re-mask.  fuse=T>1 is temporal fusion: exchange a T*r-deep
    halo ONCE, then run T stencil levels locally, each shrinking the
    extended block by r — T× fewer collective rounds (the latency bound of
    small blocks, SURVEY.md §3.2) at the cost of recomputing the
    overlapping rim.  Bit-exactness is preserved because each level
    re-zeroes out-of-image positions via the margin mask, exactly
    reproducing the oracle's ghost ring at every intermediate level.

    The block dtype is the *storage* dtype (f32, or bf16 — exact for
    quantized u8 values, half the HBM/ICI traffic); accumulation is always
    f32 inside the correlate implementations.
    """
    periodic, needs_mask = _boundary_geometry(grid, valid_hw, block_hw,
                                              boundary)
    r = filt.radius
    pallas_like = backend in ("pallas", "pallas_sep")
    sep = backend == "pallas_sep"

    def correlate_level(p, out_dtype):
        if pallas_like:
            from parallel_convolution_tpu.ops import pallas_stencil

            return pallas_stencil.correlate_padded_pallas(
                p, filt, quantize=quantize, out_dtype=out_dtype,
                separable=sep, tile=tile, interpret=interpret,
            )
        out = _XLA_CORRELATES[backend](p, filt)
        if quantize:
            out = conv.quantize_f32(out)
        return out

    def step(v):
        depth = r * fuse
        fault_point("halo_exchange")  # trace-time: a launch-build failure
        p = halo.halo_exchange(v, depth, grid, boundary)
        if pallas_like and fuse > 1:
            # All T levels inside one kernel: one HBM round trip per chunk.
            from parallel_convolution_tpu.ops import pallas_stencil

            off = jnp.stack([
                lax.axis_index("x") * block_hw[0],
                lax.axis_index("y") * block_hw[1],
            ]).astype(jnp.int32)

            return pallas_stencil.fused_iterate_pallas(
                p, off, filt, fuse, None if periodic else tuple(valid_hw),
                quantize=quantize, out_dtype=v.dtype, separable=sep,
                tile=tile, interpret=interpret,
            )
        for t in range(fuse):
            margin = depth - r * (t + 1)
            p = correlate_level(p, v.dtype)
            if not periodic and (needs_mask or margin > 0):
                p = p * _valid_mask(valid_hw, block_hw, margin).astype(p.dtype)
        return p.astype(v.dtype)

    return step


def _tap_form(backend: str, filt: Filter, quantize: bool) -> str:
    """The tap form a backend's stencil is built with: for the Pallas
    kernels ``pallas_stencil.tap_form`` (``pallas_sep`` always asks for
    the rank-1 form, ``pallas_rdma`` where ``rank1_exact`` holds), for
    the XLA forms the one each runs."""
    if backend in ("pallas", "pallas_sep", "pallas_rdma"):
        from parallel_convolution_tpu.ops import pallas_stencil

        sep = backend == "pallas_sep" or (
            backend == "pallas_rdma"
            and pallas_stencil.rank1_exact(filt, quantize))
        return pallas_stencil.tap_form(filt, sep, quantize).name
    if backend == "separable" and filt.separable() is not None:
        return "separable"
    return "direct"


def _make_block_step(filt: Filter, grid, valid_hw, block_hw, quantize: bool,
                     backend: str, fuse: int = 1, boundary: str = "zero",
                     tile: tuple[int, int] | None = None,
                     interpret: bool | None = None,
                     overlap: bool = False,
                     col_mode: str = "strided"):
    """One smoothing-step builder, dispatched through the kernel-form
    registry (``parallel.kernels``): ``(rank=2, backend, boundary)``
    resolves to the registered form, whose ``build`` returns the
    per-block step function.  Unknown backends/boundaries fail HERE with
    the registry's ValueError naming what exists — the old if-ladder's
    error surface, now covering every registered stencil form."""
    form = kernel_forms.resolve(2, backend, boundary)
    if form.stencil_form != "smooth":
        raise ValueError(
            f"kernel form {backend!r} is a {form.stencil_form} operator, "
            "not a smoother; transfer operators are driven by "
            "solvers.multigrid, not the iterate path")
    return form.build(filt, grid, valid_hw, block_hw, quantize, fuse,
                      boundary, tile, interpret, overlap, col_mode)


def _mesh_interpret(mesh: Mesh) -> bool:
    """interpret= for Pallas kernels compiled for THIS mesh's devices.

    The global default backend can be a TPU while the mesh is a forced-CPU
    one (utils.platform.cpu_devices in a process that already initialized
    the TPU backend) — resolving off jax.devices() there hands Mosaic
    kernels to the CPU lowering, which rejects them.
    """
    from parallel_convolution_tpu.utils.platform import device_on_tpu

    return not device_on_tpu(mesh.devices.flat[0])


def _check_block_size(filt: Filter, block_hw) -> None:
    if min(block_hw) < filt.radius:
        raise ValueError(
            f"per-device block {block_hw} smaller than filter radius "
            f"{filt.radius}; use a smaller mesh for this image"
        )


@lru_cache(maxsize=64)
def _build_iterate(mesh: Mesh, filt: Filter, iters: int, quantize: bool,
                   valid_hw, block_hw, backend: str, fuse: int = 1,
                   boundary: str = "zero",
                   tile: tuple[int, int] | None = None,
                   overlap: bool = False,
                   col_mode: str = "strided"):
    """Compile the fixed-count iteration runner for one (mesh, config).

    ``overlap`` and ``col_mode`` must already be RESOLVED
    (``resolve_overlap`` / ``resolve_col_mode``) — this layer compiles
    exactly what it is told, so the stamped knobs and the executable can
    never disagree.

    The runner keeps what its halo exchanges built: ``halo_counts`` maps
    each traced per-device block (shape, dtype) to the
    :class:`halo.HaloCount` of one whole call (every chunk and the tail).
    ``tap_form`` names the stencil's tap arithmetic (:func:`_tap_form`);
    ``launch_args(planes, dtype)``, on a form that describes its launch
    (``pallas_rdma``: kernel, column transport, overlap, block), what the
    build span says of a full chunk, else None.  ``sharded`` is the
    un-jitted ``shard_map`` it compiles, which :func:`_build_job` builds
    on.
    """
    # Consulted only on lru_cache misses — i.e. exactly when a fresh
    # trace/compile happens, the event the 'backend_compile' site models.
    fault_point("backend_compile")
    grid = grid_shape(mesh)
    _check_block_size(filt, block_hw)
    fuse = max(1, min(fuse, iters or 1))
    if min(block_hw) < filt.radius * fuse:
        raise ValueError(
            f"fuse={fuse} needs blocks >= {filt.radius * fuse}, got {block_hw}"
        )
    _note_compile("iterate", backend, grid, iters, fuse, boundary, block_hw)
    interp = _mesh_interpret(mesh)
    chunk = _make_block_step(filt, grid, valid_hw, block_hw, quantize,
                             backend, fuse, boundary, tile, interp, overlap,
                             col_mode)
    launch_args = getattr(chunk, "launch_args", None)
    n_chunks, rem = divmod(iters, fuse)
    tail = (_make_block_step(filt, grid, valid_hw, block_hw, quantize,
                             backend, rem, boundary, tile, interp, overlap,
                             col_mode)
            if rem else None)

    counted: dict = {}   # the last trace of each step wins: no doubling

    def count(name, fn):
        def run(v):
            with halo.counting() as c:
                out = fn(v)
            counted[name] = c
            return out
        return run

    chunk = count("chunk", chunk)
    if tail is not None:
        tail = count("tail", tail)
    halo_counts: dict = {}

    def body(block):
        key = (tuple(block.shape), jnp.dtype(block.dtype))
        block = lax.fori_loop(0, n_chunks, lambda _, v: chunk(v), block)
        if tail is not None:
            block = tail(block)
        zero = halo.HaloCount()
        halo_counts[key] = (counted.get("chunk", zero) * n_chunks
                            + counted.get("tail", zero))
        return block

    sharded = shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,  # pallas interpret-mode slices trip the vma checker
    )
    runner = jax.jit(sharded, donate_argnums=0)
    runner.halo_counts = halo_counts
    runner.tap_form = _tap_form(backend, filt, quantize)
    runner.launch_args = launch_args
    runner.sharded = sharded
    return runner


@lru_cache(maxsize=64)
def _build_converge(mesh: Mesh, filt: Filter, tol: float, max_iters: int,
                    check_every: int, quantize: bool, valid_hw, block_hw,
                    backend: str, boundary: str = "zero", fuse: int = 1,
                    tile: tuple[int, int] | None = None,
                    overlap: bool = False,
                    col_mode: str = "strided"):
    """Compile the run-to-convergence runner (C6: every-N diff + allreduce).

    ``fuse``/``tile`` are the flagship iteration knobs (temporal fusion,
    kernel tile), valid here too: a check_every-iteration chunk runs as
    floor((n-1)/fuse) fused steps + the remainder as single steps + ONE
    final single step that forms the (prev, cur) convergence pair — so any
    fuse ≥ 1 works for any check_every and the iterate stays bit-identical
    to fuse=1 (fused steps are exact, tested in test_sharded.py).
    """
    fault_point("backend_compile")  # lru_cache miss == a fresh compile
    grid = grid_shape(mesh)
    _check_block_size(filt, block_hw)
    # A chunk fuses at most the n-1 pre-pair iterations (the final one is
    # always a single step so the (prev, cur) diff exists), so clamp to
    # check_every - 1 — otherwise fuse == check_every would silently run
    # every iteration unfused ((n-1)//fuse == 0).
    requested_fuse = fuse
    fuse = max(1, min(fuse, check_every - 1))
    if min(block_hw) < filt.radius * fuse:
        clamp_note = (f" (fuse={requested_fuse} clamped to {fuse}: a "
                      f"check_every={check_every} chunk fuses at most its "
                      "n-1 pre-pair iterations)"
                      if fuse != requested_fuse else "")
        raise ValueError(
            f"fuse={fuse} needs blocks >= {filt.radius * fuse}, got "
            f"{block_hw}{clamp_note}"
        )
    _note_compile("converge", backend, grid, max_iters, fuse, boundary,
                  block_hw)
    interp = _mesh_interpret(mesh)
    step = _make_block_step(filt, grid, valid_hw, block_hw, quantize, backend,
                            boundary=boundary, tile=tile, interpret=interp,
                            overlap=overlap, col_mode=col_mode)
    fused = (_make_block_step(filt, grid, valid_hw, block_hw, quantize,
                              backend, fuse, boundary, tile, interp, overlap,
                              col_mode)
             if fuse > 1 else None)

    def body(block):
        def chunk(carry):
            cur, done, _ = carry
            n = jnp.minimum(check_every, max_iters - done)

            # Carry ONE buffer through the loop and form the (prev, cur)
            # diff pair only at the chunk boundary: carrying the pair
            # through fori_loop copies a full block every iteration
            # (measured 8x the stencil cost at 8192² on v5e — 45 ms/iter
            # vs 5.7 for the fixed-count path).
            if fused is None:
                prev = lax.fori_loop(0, n - 1, lambda _, v: step(v), cur)
            else:
                prev = lax.fori_loop(0, (n - 1) // fuse,
                                     lambda _, v: fused(v), cur)
                prev = lax.fori_loop(0, (n - 1) % fuse,
                                     lambda _, v: step(v), prev)
            cur = step(prev)
            # The MPI_Allreduce: global max of one iteration's change.
            delta = jnp.abs(cur.astype(jnp.float32) - prev.astype(jnp.float32))
            diff = lax.pmax(jnp.max(delta), AXES)
            return cur, done + n, diff

        def cond(carry):
            _, done, diff = carry
            return (done < max_iters) & (diff >= tol)

        init = (block, jnp.int32(0), jnp.float32(jnp.inf))
        cur, done, _ = lax.while_loop(cond, chunk, init)
        return cur, lax.pmax(done, AXES)

    sharded = shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES),
        out_specs=(P(None, *AXES), P()),
        check_vma=False,  # pallas interpret-mode slices trip the vma checker
    )
    return jax.jit(sharded, donate_argnums=0)


@lru_cache(maxsize=64)
def _build_converge_chunk(mesh: Mesh, filt: Filter, n: int, quantize: bool,
                          valid_hw, block_hw, backend: str,
                          boundary: str = "zero", fuse: int = 1,
                          tile: tuple[int, int] | None = None,
                          overlap: bool = False,
                          col_mode: str = "strided"):
    """Compile ONE convergence chunk: ``n`` iterations + the (prev, cur)
    max-abs diff, returned to the host.

    The progressive counterpart of :func:`_build_converge`: instead of
    the whole ``while_loop`` living on-device, each ``check_every``-sized
    chunk is its own fenced call so the HOST can observe the intermediate
    field (stream a snapshot, decide to stop, checkpoint...).  The chunk
    math is identical to one iteration of ``_build_converge``'s loop body
    — n-1 iterations (fused where legal) then one single step forming the
    (prev, cur) diff pair — so a host-driven chunk loop produces the same
    bytes as the compiled while_loop, which ``tests/test_router.py``
    asserts.  ``tol`` is NOT baked in: the host compares, so one compiled
    chunk serves every tolerance.
    """
    fault_point("backend_compile")  # lru_cache miss == a fresh compile
    grid = grid_shape(mesh)
    _check_block_size(filt, block_hw)
    # Fuse at most the n-1 pre-pair iterations (same rule as
    # _build_converge); a 1-iteration chunk has no pre-pair work at all.
    fuse = max(1, min(fuse, max(1, n - 1)))
    if min(block_hw) < filt.radius * fuse:
        raise ValueError(
            f"fuse={fuse} needs blocks >= {filt.radius * fuse}, got "
            f"{block_hw}")
    _note_compile("converge_chunk", backend, grid, n, fuse, boundary,
                  block_hw)
    interp = _mesh_interpret(mesh)
    step = _make_block_step(filt, grid, valid_hw, block_hw, quantize, backend,
                            boundary=boundary, tile=tile, interpret=interp,
                            overlap=overlap, col_mode=col_mode)
    fused = (_make_block_step(filt, grid, valid_hw, block_hw, quantize,
                              backend, fuse, boundary, tile, interp, overlap,
                              col_mode)
             if fuse > 1 and n > 1 else None)

    def body(block):
        if fused is None:
            prev = lax.fori_loop(0, n - 1, lambda _, v: step(v), block)
        else:
            prev = lax.fori_loop(0, (n - 1) // fuse,
                                 lambda _, v: fused(v), block)
            prev = lax.fori_loop(0, (n - 1) % fuse,
                                 lambda _, v: step(v), prev)
        cur = step(prev)
        delta = jnp.abs(cur.astype(jnp.float32) - prev.astype(jnp.float32))
        diff = lax.pmax(jnp.max(delta), AXES)
        return cur, diff

    sharded = shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES),
        out_specs=(P(None, *AXES), P()),
        check_vma=False,  # pallas interpret-mode slices trip the vma checker
    )
    return jax.jit(sharded, donate_argnums=0)


# Iteration-carry dtypes.  Quantized states are exact small integers, so
# narrower carries lose nothing: bf16 holds 0..255 exactly at half the
# HBM/ICI traffic of f32, and u8 — the reference's own ``unsigned char``
# buffer dtype — at a quarter (accumulation is always f32 inside the
# correlate implementations; u8 additionally requires quantize=True, checked
# in the entry points below).
STORAGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "u8": jnp.uint8}

# The jax-free registry (which the CLI/RunConfig validate against) and the
# dtype map here must never drift: a name accepted there but missing here
# would KeyError deep inside _prepare.
from parallel_convolution_tpu.utils.config import STORAGES as _STORAGES  # noqa: E402

if tuple(STORAGE_DTYPES) != _STORAGES:  # not assert: must survive python -O
    raise RuntimeError(
        f"storage registries drifted: {tuple(STORAGE_DTYPES)} != {_STORAGES}")


def _correlate_padded_xla(padded: jnp.ndarray, filt: Filter) -> jnp.ndarray:
    r = filt.radius
    lhs = padded.astype(jnp.float32)[:, None, :, :]
    rhs = jnp.asarray(filt.taps, jnp.float32)[None, None]
    out = lax.conv_general_dilated(
        lhs, rhs, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST,
    )
    return out[:, 0]


# The pure-XLA correlate implementations, keyed by form name (consumed by
# _build_halo_step; the old _correlate_for_backend ladder).
_XLA_CORRELATES = {
    "shifted": conv.correlate_padded,
    "xla_conv": _correlate_padded_xla,
    "separable": conv.correlate_padded_separable,
}


def _register_smoother_forms() -> None:
    """Install the six historical backends as rank-2 smoother forms.

    This IS the old if-ladder, stated once as data: each backend name
    maps to its builder, and ``pallas_rdma`` alone declares the
    overlapped-pipeline capability bit (the knowledge the three
    per-call-site clamps used to re-derive by string comparison)."""
    from functools import partial

    from parallel_convolution_tpu.utils.config import BOUNDARIES

    for name in BACKENDS:
        kernel_forms.register(kernel_forms.KernelForm(
            name=name, rank=2, stencil_form="smooth",
            boundaries=tuple(BOUNDARIES),
            overlap_capable=(name == "pallas_rdma"),
            persistent_capable=(name == "pallas_rdma"),
            tile_aligned_blocks=(name == "pallas_rdma"),
            build=(_build_rdma_step if name == "pallas_rdma"
                   else partial(_build_halo_step, name))))


_register_smoother_forms()


# Module-scope so jit's function-identity cache holds: a per-call lambda
# would retrace + recompile the reducer on every contract check.
_minmax_f32 = jax.jit(
    lambda a: jnp.stack([jnp.min(a), jnp.max(a)]).astype(jnp.float32))


def _check_quantize_contract(xs, filt: Filter, quantize: bool,
                             storage=None) -> None:
    """Fail loudly on out-of-contract quantize-mode inputs.

    ``quantize=True`` assumes u8-range pixel values (a decoded image,
    SURVEY §2 C1 semantics).  Convex filters elide the provably-idle
    store-back clamp, so a float plane with values outside [0, 255] would
    propagate UNCLAMPED where pre-elision code clamped it on the first
    store-back — silently different bytes.  One min/max reduce over the
    input per run (negligible vs the iterations) turns that into an error.
    Traced callers skip the check: the contract stays documented but is
    unverifiable mid-trace.

    ``storage`` is the dtype ``xs`` will be cast to before it iterates
    (default: its own).  The cast rounds monotonically, so the extremes of
    the cast values are the cast extremes: the check reads ``xs`` as it is.
    """
    storage = jnp.dtype(xs.dtype if storage is None else storage)
    if not (quantize and filt.convex) or storage == jnp.uint8:
        return
    if isinstance(xs, jax.core.Tracer):
        return
    # One fused device reduction + one 2-float readback (separate min/max
    # dispatches would each stream the whole array from HBM).
    lo, hi = (float(v) for v in np.asarray(_minmax_f32(xs)).astype(storage))
    if lo < 0.0 or hi > 255.0:
        raise ValueError(
            f"quantize=True input has values in [{lo}, {hi}], outside the "
            "u8 contract [0, 255]: convex filters elide the store-back "
            "clamp, so out-of-range values would propagate unclamped. "
            "Clamp the input (or use quantize=False for float planes)."
        )


def _check_storage(storage: str, quantize: bool) -> None:
    if storage == "u8" and not quantize:
        raise ValueError(
            "storage='u8' requires quantize=True: u8 carries can only hold "
            "the quantized integer states; a float iterate would be "
            "silently truncated every iteration"
        )


def block_extent(hw, grid, backend: str = "shifted", storage: str = "f32",
                 boundary: str = "zero") -> tuple[int, int]:
    """The per-device block of an (H, W) image on a ``grid``: the mesh's
    share, rounded up to the multiple the backend's kernel form needs
    (``kernels.block_multiple``; a zero boundary only — padding a torus
    would move its wrap).  The rim beyond the image is masked, so the
    bytes are the same on any block."""
    R, Cc = grid
    br, bc = -(-int(hw[0]) // R), -(-int(hw[1]) // Cc)
    if boundary != "zero":
        return br, bc
    ar, ac = kernel_forms.block_multiple(backend, storage)
    return padded_extent(br, ar), padded_extent(bc, ac)


def _prepare(x, mesh: Mesh, launch: "Launch"):
    """Pad a global (C, H, W) image to the launch's blocks and shard it:
    the block a runner is built for is always the block it is fed."""
    x = jnp.asarray(x, STORAGE_DTYPES[launch.storage])
    C, H, W = x.shape
    R, Cc = grid_shape(mesh)
    bh, bw = launch.block_hw
    Hp, Wp = bh * R, bw * Cc
    if Hp < H or Wp < W:
        raise ValueError(
            f"launch block {launch.block_hw} on grid {(R, Cc)} does not "
            f"hold a {H}x{W} image")
    if (Hp, Wp) != (H, W):
        x = jnp.pad(x, ((0, 0), (0, Hp - H), (0, Wp - W)))
    x = jax.device_put(x, block_sharding(mesh))
    return x, (H, W), (bh, bw)


def reshard_prepared(xs, valid_hw, mesh: Mesh):
    """Move an already-prepared padded (C, Hp, Wp) array onto a DIFFERENT
    mesh: crop to the valid extent, re-pad to the new grid's block
    multiples, and re-shard (elastic recovery's in-memory counterpart of
    the checkpoint reshard — e.g. a serving engine shrinking mid-process
    without a disk round-trip).

    Bit-exact by the masking invariant: positions outside ``valid_hw``
    are zero on every grid, so crop + zero-re-pad reproduces exactly the
    state ``_prepare`` would have built on ``mesh`` from the valid
    pixels.  Compiled state for other meshes is untouched — the build
    caches key on the mesh, so swapping BACK later reuses the old
    executables.

    Materializes ONE host copy of the cropped state (a few MB at serving
    sizes); huge-image states should reshard through the checkpoint path
    instead (``utils.checkpoint.load_state``), which streams per-shard
    files and never holds the full image on one host.
    """
    H, W = (int(v) for v in valid_hw)
    R, Cc = grid_shape(mesh)
    Hp, Wp = padded_extent(H, R), padded_extent(W, Cc)
    x = xs[:, :H, :W]
    if (Hp, Wp) != (H, W):
        x = jnp.pad(x, ((0, 0), (0, Hp - H), (0, Wp - W)))
    return jax.device_put(np.asarray(x), block_sharding(mesh))


def _norm_tile(tile) -> tuple[int, int] | None:
    """Normalize a (TH, TW) kernel-tile override to a hashable tuple."""
    if tile is None:
        return None
    th, tw = (int(v) for v in tile)
    if th <= 0 or tw <= 0:
        raise ValueError(f"tile extents must be positive, got {(th, tw)}")
    return (th, tw)


def _storage_name(dtype) -> str:
    """The STORAGE_DTYPES name for an array dtype (default 'f32')."""
    for name, dt in STORAGE_DTYPES.items():
        if jnp.dtype(dt) == jnp.dtype(dtype):
            return name
    return "f32"


@dataclasses.dataclass(frozen=True)
class Launch:
    """One resolved launch: the knobs its runner is built with and the
    per-device block the image is padded to, as :func:`resolve_launch`
    decides them.  ``plan_source`` is where an ``auto`` backend's plan
    came from (measured|interpolated|predicted), else ``explicit``."""

    backend: str
    fuse: int
    tile: tuple[int, int] | None
    overlap: bool
    col_mode: str
    storage: str
    boundary: str
    block_hw: tuple[int, int]
    plan_source: str = "explicit"


def resolve_launch(mesh: Mesh, filt: Filter, shape, *,
                   backend: str = "shifted", storage: str = "f32",
                   quantize: bool = True, boundary: str = "zero",
                   fuse: int | None = 1,
                   tile: tuple[int, int] | None = None,
                   overlap: bool | None = None, col_mode: str | None = None,
                   fallback: bool = False, check_every: int | None = None,
                   plans=None, block_hw=None) -> Launch:
    """The one place a launch of a (C, H, W) image of ``shape`` is decided.

    In order: ``backend="auto"`` resolves through the tuning subsystem
    (``plans``, else the ambient plan cache, else the cost model;
    ``fuse=None``/``tile=None`` are then tuned too, other values are
    pins; ``check_every`` marks a convergence workload); the overlap
    request is clamped (:func:`resolve_overlap`); the block is
    :func:`block_extent`'s unless the caller states its own
    ``block_hw``; the column transport is chosen
    (:func:`resolve_col_mode`); with ``fallback`` the degrade walk
    (``resilience.degrade``) probes the launch on that block and may
    land on a lower tier, and overlap and column transport are then
    re-clamped for the tier that survived.
    """
    if storage not in STORAGE_DTYPES:
        raise ValueError(f"storage must be one of {tuple(STORAGE_DTYPES)}, "
                         f"got {storage!r}")
    C, H, W = (int(v) for v in shape)
    tile = _norm_tile(tile)
    if backend != AUTO:
        source = "explicit"
    else:
        from parallel_convolution_tpu import tuning

        res = tuning.resolve(
            mesh, filt, (C, H, W), storage=storage, quantize=quantize,
            boundary=boundary, fuse=fuse, tile=tile, overlap=overlap,
            col_mode=col_mode, plans=plans, check_every=check_every)
        backend, fuse, tile, source = (res.backend, res.fuse,
                                       _norm_tile(res.tile), res.source)
        overlap, col_mode = res.overlap, res.col_mode
    fuse = 1 if fuse is None else int(fuse)
    overlap = resolve_overlap(overlap, backend, mesh)
    if block_hw is None:
        block_hw = block_extent((H, W), grid_shape(mesh), backend, storage,
                                boundary)
    block_hw = (int(block_hw[0]), int(block_hw[1]))
    col_mode = resolve_col_mode(col_mode, backend, mesh, block_hw,
                                filt.radius, fuse, storage)
    if fallback:
        from parallel_convolution_tpu.resilience import degrade

        backend = degrade.resolve_backend(
            mesh, filt, backend, quantize=quantize, fuse=fuse,
            boundary=boundary, tile=tile, storage=storage,
            block_hw=block_hw, overlap=overlap, col_mode=col_mode)
        overlap = kernel_forms.clamp_overlap(overlap, backend)
        col_mode = clamp_col_mode(col_mode, backend)
    return Launch(backend=backend, fuse=fuse, tile=tile, overlap=overlap,
                  col_mode=col_mode, storage=storage, boundary=boundary,
                  block_hw=block_hw, plan_source=source)


def iterate_prepared(xs, filt: Filter, iters: int, mesh: Mesh,
                     valid_hw, quantize: bool = True,
                     backend: str = "shifted", fuse: int | None = 1,
                     boundary: str = "zero",
                     tile: tuple[int, int] | None = None,
                     check_contract: bool = True,
                     fallback: bool = False,
                     overlap: bool | None = None,
                     col_mode: str | None = None):
    """Iterate an already-sharded padded (C, Hp, Wp) array in place(-ish).

    The zero-copy entry for huge images loaded via utils.sharded_io: input
    stays in its blocked sharding, output keeps the padded extent (pass it
    straight to ``save_sharded``).  The input array is donated.

    ``check_contract=False`` skips the quantize-range input check (one
    full-array reduction) — for loop callers like
    ``utils.checkpoint.run_checkpointed`` that validated the initial state
    once and whose chunk inputs are in contract by induction (quantized
    outputs are always in [0, 255]).

    The knobs resolve through :func:`resolve_launch` on the array's own
    block: ``backend="auto"`` through the tuning subsystem, ``overlap``
    (the RDMA kernels' interior-first pipeline; None = off for explicit
    backends) and ``col_mode`` (the RDMA column-slab transport, packed |
    strided | auto; None = auto) to what actually compiles.
    ``fallback=True`` probes the launch once per (mesh, config) per
    process BEFORE the real (donating) run and, on a classified-transient
    compile/launch failure, walks the degradation chain
    ``pallas_rdma → pallas → shifted`` (resilience.degrade) — emitting a
    BackendDegradedWarning rather than dying with the first failed tier,
    and never losing the donated input to a launch that was going to
    fail.
    """
    if jnp.dtype(xs.dtype) == jnp.uint8 and not quantize:
        _check_storage("u8", quantize)  # public entry: same guard as above
    R, Cc = grid_shape(mesh)
    with obs_trace.span("pctpu.resolve"):
        launch = resolve_launch(
            mesh, filt, (xs.shape[0], *valid_hw), backend=backend,
            storage=_storage_name(xs.dtype), quantize=quantize,
            boundary=boundary, fuse=fuse, tile=tile, overlap=overlap,
            col_mode=col_mode, fallback=fallback,
            block_hw=(xs.shape[1] // R, xs.shape[2] // Cc))
    return _iterate_launch(xs, filt, iters, mesh, valid_hw, launch,
                           quantize, check_contract)


def _iterate_launch(xs, filt: Filter, iters: int, mesh: Mesh, valid_hw,
                    launch: Launch, quantize: bool,
                    check_contract: bool = True):
    """:func:`iterate_prepared` on a resolved launch: look up the runner
    and dispatch it."""
    block = ((xs.shape[0], *launch.block_hw), jnp.dtype(xs.dtype))
    with obs_trace.span("pctpu.step.build") as sp:
        if check_contract:
            _check_quantize_contract(xs, filt, quantize)
        fn = _iterate_runner(mesh, filt, iters, quantize, valid_hw, launch)
        sp.set(tap_form=fn.tap_form)
        if fn.launch_args is not None and obs_metrics.enabled():
            sp.set(**fn.launch_args(xs.shape[0], block[1]))
        if block not in fn.halo_counts:
            sp.set(compiled=1)  # the dispatch below traces and compiles
    # The dispatch stays asynchronous: no fence here (callers overlap the
    # next chunk's work with device execution); wall-based series come
    # from the callers that already fence.  The halo counts are what the
    # runner's trace built, so counting costs no model arithmetic.
    with obs_trace.span("pctpu.step.dispatch") as sp:
        out = fn(xs)
        if obs_metrics.enabled():
            count = fn.halo_counts[block]
            sp.set(exchanges=count.rounds, halo_bytes=count.total_bytes)
            if count.wire_bytes is not None:
                sp.set(rdma_wire_bytes=count.wire_bytes)
    if obs_metrics.enabled():
        from parallel_convolution_tpu.obs import attribution

        attribution.record_halo(launch.backend, count.bytes, count.rounds,
                                iters, count.wire_bytes)
    return out


def _iterate_runner(mesh, filt, iters, quantize, valid_hw, launch: Launch):
    """The fixed-count runner a resolved launch builds (cached)."""
    return _build_iterate(mesh, filt, iters, quantize, tuple(valid_hw),
                          launch.block_hw, launch.backend, launch.fuse,
                          launch.boundary, launch.tile, launch.overlap,
                          launch.col_mode)


def iterate_text(shape, filt: Filter, iters: int, mesh: Mesh | None = None,
                 quantize: bool = True, backend: str = "shifted",
                 storage: str = "f32", fuse: int | None = 1,
                 boundary: str = "zero",
                 tile: tuple[int, int] | None = None,
                 fallback: bool = False,
                 overlap: bool | None = None,
                 col_mode: str | None = None) -> str:
    """The compiled text of the job program that :func:`sharded_iterate`
    runs for a (C, H, W) float32 image of ``shape`` with these knobs —
    what :func:`op_scopes` reads to join a device trace's ops to their
    layer.  After a run of the same shape jit's caches hold the
    executable, so this compiles nothing new."""
    if mesh is None:
        mesh = make_grid_mesh()
    launch = resolve_launch(
        mesh, filt, shape, backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        overlap=overlap, col_mode=col_mode, fallback=fallback)
    return _launch_text(shape, filt, iters, mesh, launch, quantize)


def _launch_text(shape, filt: Filter, iters: int, mesh: Mesh,
                 launch: Launch, quantize: bool) -> str:
    """:func:`iterate_text` of a resolved launch: the job program
    (:func:`_build_job`) lowered for a float32 image in the mesh's block
    sharding, the sharding of an image laid out for the grid (an image
    the grid does not divide has none, and is lowered unsharded)."""
    shape = tuple(int(v) for v in shape)
    runner = _iterate_runner(mesh, filt, iters, quantize, shape[1:], launch)
    fn = _build_job(runner, mesh, launch, shape, jnp.dtype(jnp.float32))
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=fn.sharding)
    return fn.lower(arg).compile().as_text()


_HLO_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_HLO_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([^\s,})]+)")


def op_scopes(compiled_text: str) -> dict[str, str]:
    """``{instruction name: scope}`` of a compiled executable's text.

    The scope is the innermost ``pctpu.*`` named scope in the
    instruction's ``op_name`` metadata (``pctpu.halo``,
    ``pctpu.kernel_io``, ``pctpu.job_io``), "" where there is none.  A
    fusion takes its root's ``op_name``.  A device trace's op events
    carry the same instruction names (``pad.20``, ``concatenate.7``), so
    this reads a trace by layer."""
    own: dict[str, str | None] = {}
    fusion_calls: dict[str, str] = {}
    root_of: dict[str, str] = {}
    comp = ""
    for line in compiled_text.splitlines():
        if not line.startswith(" "):
            m = _HLO_HEADER.match(line)
            if m:
                comp = m.group(1)
            continue
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        name = m.group(2)
        op = _HLO_OP_NAME.search(line)
        own[name] = op.group(1) if op else None
        calls = _HLO_CALLS.search(line)
        if calls and " fusion(" in line:
            fusion_calls[name] = calls.group(1)
        if m.group(1):
            root_of[comp] = name
    out = {}
    for name, op_name in own.items():
        root = root_of.get(fusion_calls.get(name, ""))
        if root is not None and own.get(root) is not None:
            op_name = own[root]
        scopes = [p for p in (op_name or "").split("/")
                  if p.startswith("pctpu.")]
        out[name] = scopes[-1] if scopes else ""
    return out


def sharded_iterate(x, filt: Filter, iters: int, mesh: Mesh | None = None,
                    quantize: bool = True, backend: str = "shifted",
                    storage: str = "f32", fuse: int | None = 1,
                    boundary: str = "zero",
                    tile: tuple[int, int] | None = None,
                    fallback: bool = False,
                    overlap: bool | None = None,
                    col_mode: str | None = None):
    """Run ``iters`` stencil iterations of a global (C, H, W) f32 image
    sharded over the 2D mesh.  Returns the global (C, H, W) f32 result
    (bit-identical to the serial oracle for any mesh shape).

    ``storage='bf16'`` halves HBM/ICI traffic by carrying the state in
    bfloat16 between iterations — still bit-exact in quantize mode (u8
    values are exact in bf16); in float mode it is a documented
    precision/bandwidth trade.  ``tile=(TH, TW)`` overrides the Pallas
    kernels' VMEM output-tile shape (the scripts/tune_pallas.py knob);
    None = the per-kernel tuned default.  The knobs resolve through
    :func:`resolve_launch`.

    ``quantize=True`` is the u8 store-back semantics and assumes pixel
    values in [0, 255] (a decoded u8 image): convex filters elide the
    provably-idle clamp (``Filter.convex``), so a float plane with
    out-of-range values is out of contract — it raises ValueError up
    front (``_check_quantize_contract``) rather than silently producing
    different bytes than the pre-elision code.
    """
    if mesh is None:
        mesh = make_grid_mesh()
    _check_storage(storage, quantize)
    with obs_trace.span("pctpu.resolve"):
        launch = resolve_launch(
            mesh, filt, x.shape, backend=backend, storage=storage,
            quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
            overlap=overlap, col_mode=col_mode, fallback=fallback)
    return _run_launch(x, filt, iters, mesh, launch, quantize)


@lru_cache(maxsize=64)
def _build_job(runner, mesh: Mesh, launch: Launch,
               shape: tuple[int, int, int], dtype):
    """Compile one whole :func:`sharded_iterate` job of a resolved launch
    on a (C, H, W) input of ``shape`` and ``dtype`` as ONE program: the
    cast to the launch's storage, the zero pad to its blocks (where they
    exceed the image's share of the grid), the iterations of ``runner``'s
    un-jitted body (``runner.sharded``; the donating runner itself is not
    called), the crop back to (H, W) and the cast to float32.

    The cast, pad and crop sit under the named scope ``pctpu.job_io``,
    apart from ``pctpu.halo`` and ``pctpu.kernel_io``.  The program
    donates nothing: the caller's array survives in every storage.  Its
    output is in the mesh's block sharding where the grid divides (H, W),
    else where the compiler puts it; ``sharding`` is that block sharding,
    or None where the grid does not divide the image.  ``traced`` says
    whether the program has traced; ``count()`` is the
    :class:`halo.HaloCount` of one call once it has.
    """
    C, H, W = shape
    R, Cc = grid_shape(mesh)
    bh, bw = launch.block_hw
    Hp, Wp = bh * R, bw * Cc
    if Hp < H or Wp < W:
        raise ValueError(
            f"launch block {launch.block_hw} on grid {(R, Cc)} does not "
            f"hold a {H}x{W} image")
    storage = jnp.dtype(STORAGE_DTYPES[launch.storage])
    pad = (Hp, Wp) != (H, W)
    blocks = block_sharding(mesh)

    def job(x):
        fn.traced = True    # the body runs only while jit traces
        with jax.named_scope("pctpu.job_io"):
            xs = x.astype(storage)
            if pad:
                xs = jnp.pad(xs, ((0, 0), (0, Hp - H), (0, Wp - W)))
            xs = lax.with_sharding_constraint(xs, blocks)
        out = runner.sharded(xs)
        with jax.named_scope("pctpu.job_io"):
            if pad:
                out = lax.slice(out, (0, 0, 0), (C, H, W))
            return out.astype(jnp.float32)

    grid_sharded = H % R == 0 and W % Cc == 0
    fn = jax.jit(job, out_shardings=blocks if grid_sharded else None)
    fn.sharding = blocks if grid_sharded else None
    fn.traced = False
    block = ((C, bh, bw), storage)
    fn.count = lambda: runner.halo_counts[block]
    return fn


def _run_launch(x, filt: Filter, iters: int, mesh: Mesh, launch: Launch,
                quantize: bool):
    """:func:`sharded_iterate` on a resolved launch: look up the job
    program (:func:`_build_job`) and dispatch it, one call per job."""
    if not isinstance(x, (np.ndarray, jax.Array)):
        x = np.asarray(x)
    shape = tuple(int(v) for v in x.shape)
    storage = jnp.dtype(STORAGE_DTYPES[launch.storage])
    with obs_trace.span("pctpu.step.build") as sp:
        _check_quantize_contract(x, filt, quantize, storage)
        runner = _iterate_runner(mesh, filt, iters, quantize, shape[1:],
                                 launch)
        fn = _build_job(runner, mesh, launch, shape, jnp.dtype(x.dtype))
        sp.set(tap_form=runner.tap_form)
        if runner.launch_args is not None and obs_metrics.enabled():
            sp.set(**runner.launch_args(shape[0], storage))
        if not fn.traced:
            sp.set(compiled=1)  # the dispatch below traces and compiles
    # As in _iterate_launch: no fence, and the halo counts are what the
    # program's trace built.
    with obs_trace.span("pctpu.step.dispatch") as sp:
        if (isinstance(x, jax.Array) and x.committed
                and x.sharding != fn.sharding):
            # A program takes committed input only on its own devices, in
            # their order: move an array committed elsewhere onto them.
            x = jax.device_put(x, fn.sharding or replicated(mesh))
        out = fn(x)
        if obs_metrics.enabled():
            count = fn.count()
            sp.set(exchanges=count.rounds, halo_bytes=count.total_bytes)
            if count.wire_bytes is not None:
                sp.set(rdma_wire_bytes=count.wire_bytes)
    if obs_metrics.enabled():
        from parallel_convolution_tpu.obs import attribution

        attribution.record_halo(launch.backend, count.bytes, count.rounds,
                                iters, count.wire_bytes)
    return out


def _converge_setup(x, filt: Filter, mesh: Mesh, check_every: int, *,
                    backend, storage, quantize, boundary, fuse, tile,
                    fallback, overlap, col_mode):
    """The shared set-up of both Jacobi convergence entries: the launch,
    resolved as a convergence workload, and the padded, checked state."""
    _check_storage(storage, quantize)
    launch = resolve_launch(
        mesh, filt, np.shape(x), backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        overlap=overlap, col_mode=col_mode, fallback=fallback,
        check_every=int(check_every))
    xs, valid_hw, _ = _prepare(x, mesh, launch)
    _check_quantize_contract(xs, filt, quantize)
    return launch, xs, valid_hw


def sharded_converge(x, filt: Filter, tol: float, max_iters: int,
                     check_every: int = 1, mesh: Mesh | None = None,
                     quantize: bool = False, backend: str = "shifted",
                     storage: str = "f32", boundary: str = "zero",
                     fuse: int | None = 1,
                     tile: tuple[int, int] | None = None,
                     fallback: bool = False,
                     overlap: bool | None = None, solver: str = "jacobi",
                     mg_levels: int | None = None,
                     col_mode: str | None = None):
    """Run-to-convergence (BASELINE config 5).  Returns (result, iters_run).

    ``fuse``/``tile`` mirror :func:`sharded_iterate`: fused chunks run
    between convergence checks (any fuse ≥ 1, any check_every), so config
    5 rides the same optimized kernels as the fixed-count path — including
    ``fallback=True`` backend degradation.

    ``solver="multigrid"`` dispatches to the geometric V-cycle
    (``solvers.multigrid.mg_converge``, lazily imported — the solver
    package imports this module): the returned count is then V-CYCLES
    run, ``max_iters`` bounds fine-grid work units, and ``check_every``
    is ignored (the cycle is the check cadence).  Same stopping measure
    either way: the max-abs change of one fine-grid sweep.
    """
    if solver == "multigrid":
        from parallel_convolution_tpu.solvers import multigrid

        out, res = multigrid.mg_converge(
            x, filt, tol=tol, max_iters=max_iters, mesh=mesh,
            quantize=quantize, backend=backend, storage=storage,
            boundary=boundary, fuse=fuse, tile=tile, fallback=fallback,
            overlap=overlap, mg_levels=mg_levels, col_mode=col_mode)
        return out, res.cycles
    if solver != "jacobi":
        from parallel_convolution_tpu.utils.config import SOLVERS

        raise ValueError(f"solver must be one of {SOLVERS}, got {solver!r}")
    if mesh is None:
        mesh = make_grid_mesh()
    launch, xs, valid_hw = _converge_setup(
        x, filt, mesh, check_every, backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        fallback=fallback, overlap=overlap, col_mode=col_mode)
    backend, block_hw = launch.backend, launch.block_hw
    fn = _build_converge(mesh, filt, float(tol), int(max_iters),
                         int(check_every), quantize, valid_hw, block_hw,
                         backend, boundary, launch.fuse, launch.tile,
                         launch.overlap, launch.col_mode)
    channels, shape = xs.shape[0], tuple(xs.shape)
    t0 = time.perf_counter()
    # The convergence run is fenced (the count readback), so it gets a
    # real device span: root of a fresh trace for a bare CLI call, child
    # of the caller's span when one is active.  record_step below then
    # hangs the model-attributed exchange/compute children off it.
    with obs_trace.span("device", source="sharded_converge",
                        backend=backend) as dsp:
        out, done = fn(xs)
        done = int(done)  # materializes the run (the convergence count)
        dsp.set(iters=done)
    if obs_metrics.enabled():
        with obs_trace.attach(dsp.context):
            _record_step_obs(backend, mesh, block_hw, filt.radius,
                             max(1, min(launch.fuse,
                                        max(1, check_every - 1))),
                             done, channels, storage, boundary,
                             time.perf_counter() - t0, shape, quantize,
                             launch.tile, source="sharded_converge",
                             overlap=launch.overlap,
                             col_mode=launch.col_mode)
    return out[:, : valid_hw[0], : valid_hw[1]].astype(jnp.float32), done


def sharded_converge_stream(x, filt: Filter, tol: float, max_iters: int,
                            check_every: int = 1, mesh: Mesh | None = None,
                            quantize: bool = False, backend: str = "shifted",
                            storage: str = "f32", boundary: str = "zero",
                            fuse: int | None = 1,
                            tile: tuple[int, int] | None = None,
                            fallback: bool = False,
                            overlap: bool | None = None,
                            solver: str = "jacobi",
                            mg_levels: int | None = None,
                            col_mode: str | None = None):
    """Progressive run-to-convergence: a generator over snapshot chunks.

    Yields ``(image, iters_done, diff)`` after every ``check_every``-sized
    chunk — ``image`` is the (C, H, W) float32 field at the valid extent
    (a host copy, safe to keep), ``diff`` the max-abs single-iteration
    change that the convergence decision is made on.  The stream ends
    when ``diff < tol`` or ``iters_done >= max_iters``; the LAST yielded
    image is bit-identical to :func:`sharded_converge` with the same
    arguments (same chunk math, host-driven instead of ``while_loop`` —
    the per-chunk diff readback is the fence that makes the field
    observable, which is the point: a serving tier can stream best-so-far
    results out of a long job instead of holding an all-or-nothing
    deadline).

    ``solver="multigrid"`` yields one snapshot per V-CYCLE instead
    (``iters_done`` counts cycles; ``max_iters`` bounds fine-grid work
    units); callers that need the work-unit accounting per row use
    ``solvers.multigrid.mg_converge_stream`` directly, which this
    delegates to.
    """
    if solver == "multigrid":
        from parallel_convolution_tpu.solvers import multigrid

        for out, cycles, residual, _wu in multigrid.mg_converge_stream(
                x, filt, tol=tol, max_iters=max_iters, mesh=mesh,
                quantize=quantize, backend=backend, storage=storage,
                boundary=boundary, fuse=fuse, tile=tile, fallback=fallback,
                overlap=overlap, mg_levels=mg_levels, col_mode=col_mode):
            yield (out, cycles, residual)
        return
    if solver != "jacobi":
        from parallel_convolution_tpu.utils.config import SOLVERS

        raise ValueError(f"solver must be one of {SOLVERS}, got {solver!r}")
    if mesh is None:
        mesh = make_grid_mesh()
    launch, xs, valid_hw = _converge_setup(
        x, filt, mesh, check_every, backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        fallback=fallback, overlap=overlap, col_mode=col_mode)
    check_every, max_iters = int(check_every), int(max_iters)
    done, diff = 0, float("inf")
    while done < max_iters and diff >= tol:
        n = min(check_every, max_iters - done)
        fn = _build_converge_chunk(mesh, filt, n, quantize, tuple(valid_hw),
                                   launch.block_hw, launch.backend, boundary,
                                   launch.fuse, launch.tile, launch.overlap,
                                   launch.col_mode)
        xs, d = fn(xs)
        diff = float(d)   # the readback fences the chunk
        done += n
        yield (np.asarray(xs[:, : valid_hw[0], : valid_hw[1]]
                          .astype(jnp.float32)), done, diff)
