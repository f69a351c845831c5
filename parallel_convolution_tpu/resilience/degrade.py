"""Graceful backend degradation: probe once, walk the fallback chain.

Round-5's chip story had two expensive versions of this done by hand: a
tiled-RDMA kernel whose compile crashed the chipless helper (a transient,
healed by retry/fallback), and two driver rounds whose headline row was a
silent CPU fallback nobody noticed until the evidence audit.  The policy
here makes both impossible to repeat silently:

* Each (mesh, backend, config) is probed ONCE per process — a tiny
  sharded end-to-end compile + run — and the verdict (or the exception)
  is cached, the same pattern the magic-round byte-guard established
  (``pallas_stencil._compiled_magic_ok``).
* On a classified-**transient** probe failure the chain walks
  ``pallas_rdma → pallas → shifted`` (separable tiers rejoin at
  ``pallas``), emitting a structured :class:`BackendDegradedWarning`.
* **Terminal** failures (config/shape/contract errors) raise immediately:
  degradation must never paper over a programming error.
* The resolved name is returned to the caller, and ``utils.bench`` stamps
  it into every row as ``effective_backend`` — a fallback can no longer
  masquerade as the requested tier in published numbers.
"""

from __future__ import annotations

import warnings

from parallel_convolution_tpu.obs import events as obs_events, metrics as obs_metrics
from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.resilience.retry import (
    TERMINAL, RetryExhausted, classify,
)

__all__ = [
    "BackendDegradedWarning", "clear_probe_cache", "degradation_chain",
    "probe_backend", "resolve_backend",
]


class BackendDegradedWarning(UserWarning):
    """A requested backend failed transiently and a lower tier was used."""


# Next tier down for each backend.  The separable tiers rejoin at the
# plain 2D Pallas kernel rather than each other: pallas_sep's rank-1
# rounding order is only byte-identical for dyadic filters in quantize
# mode, so degrading INTO it could change bytes — degrading out of any
# Pallas tier to 'shifted' (the normative XLA path) never can.
_FALLBACK_NEXT = {
    "pallas_rdma": "pallas",
    "pallas_sep": "pallas",
    "pallas": "shifted",
    "xla_conv": "shifted",
    "separable": "shifted",
}


def degradation_chain(backend: str) -> tuple[str, ...]:
    """The orderly walk from ``backend`` down to the normative path."""
    chain = [backend]
    while chain[-1] in _FALLBACK_NEXT:
        chain.append(_FALLBACK_NEXT[chain[-1]])
    return tuple(chain)


# (mesh, filter, backend, config) -> None on success, or the exception the
# probe raised.  Caching the FAILURE too keeps the walk deterministic
# within a process: a flaky compile that failed once stays failed until
# the process (or the cache) is reset, mirroring how the magic-round
# guard latches its verdict.
_PROBE_CACHE: dict[tuple, BaseException | None] = {}


def clear_probe_cache() -> None:
    _PROBE_CACHE.clear()
    _LAST_RESOLVED.clear()


def _probe_key(mesh, filt: Filter, backend: str, quantize, fuse, boundary,
               tile, storage, block_hw, overlap=False,
               col_mode="packed") -> tuple:
    return (mesh, filt.name, filt.radius, backend, bool(quantize), int(fuse),
            boundary, tile, storage, block_hw, bool(overlap), str(col_mode))


def probe_backend(mesh, filt: Filter, backend: str, *, quantize: bool = True,
                  fuse: int = 1, boundary: str = "zero",
                  tile: tuple[int, int] | None = None,
                  storage: str = "f32",
                  block_hw: tuple[int, int] | None = None,
                  overlap: bool = False,
                  col_mode: str = "packed") -> None:
    """Compile + run one ``fuse``-iteration sharded chunk of ``backend``.

    Raises whatever the compile/launch raised (replayed from cache on
    repeat calls); returns None on (possibly cached) success.

    ``block_hw`` is the REAL run's per-device block: kernel selection
    depends on it (e.g. ``pallas_rdma`` auto-switches to the tiled HBM
    kernel — the round-5 silicon compile-crash class — only past its VMEM
    bound), so the probe must compile the same kernel family and storage
    dtype the real run will, not a miniature.  Callers inside the library
    always pass it; ``None`` falls back to the fused slab floor
    (``max(8, radius*fuse)`` per side) for standalone use.  Cost: one
    compile + ``fuse`` iterations on a zeros block, once per (mesh,
    backend, config) per process.
    """
    launch = _tier_launch(mesh, filt, backend, quantize, fuse, boundary,
                          tile, storage, block_hw, overlap, col_mode)
    key = _probe_key(mesh, filt, launch.backend, quantize, launch.fuse,
                     launch.boundary, launch.tile, launch.storage,
                     launch.block_hw, launch.overlap, launch.col_mode)
    if key in _PROBE_CACHE:
        err = _PROBE_CACHE[key]
        if err is not None:
            raise err
        return
    try:
        _run_probe(mesh, filt, launch, quantize)
    except Exception as e:  # noqa: BLE001 — the verdict IS the product
        _PROBE_CACHE[key] = e
        raise
    _PROBE_CACHE[key] = None


def _tier_launch(mesh, filt, backend, quantize, fuse, boundary, tile,
                 storage, block_hw, overlap, col_mode):
    """The launch the resolver makes for one walked tier on the caller's
    block: overlap and column transport clamp to what the tier has."""
    from parallel_convolution_tpu.parallel import step as step_lib
    from parallel_convolution_tpu.parallel.mesh import grid_shape

    grid = grid_shape(mesh)
    fuse = max(1, int(fuse))
    if block_hw is None:
        b = max(8, filt.radius * fuse)
        hw = (grid[0] * b, grid[1] * b)
    else:
        hw = (grid[0] * block_hw[0], grid[1] * block_hw[1])
    return step_lib.resolve_launch(
        mesh, filt, (1, *hw), backend=backend, storage=storage,
        quantize=quantize, boundary=boundary, fuse=fuse, tile=tile,
        overlap=overlap, col_mode=col_mode, block_hw=block_hw)


def _run_probe(mesh, filt, launch, quantize) -> None:
    import jax
    import numpy as np

    from parallel_convolution_tpu.parallel import step as step_lib
    from parallel_convolution_tpu.parallel.mesh import grid_shape

    R, C = grid_shape(mesh)
    bh, bw = launch.block_hw
    xs, valid_hw, _ = step_lib._prepare(
        np.zeros((1, R * bh, C * bw), np.float32), mesh, launch)
    fn = step_lib._iterate_runner(mesh, filt, launch.fuse, quantize,
                                  valid_hw, launch)
    jax.block_until_ready(fn(xs))


# requested-backend -> effective-backend of the most recent resolution in
# this process; lets entry points (CLI checkpoint branch) label their
# output without re-deriving the probe key.
_LAST_RESOLVED: dict[str, str] = {}


def effective_for(requested: str) -> str | None:
    """The effective backend of this process's last resolution of
    ``requested`` (None if it was never resolved)."""
    return _LAST_RESOLVED.get(requested)


def resolve_backend(mesh, filt: Filter, backend: str, *, quantize: bool = True,
                    fuse: int = 1, boundary: str = "zero",
                    tile: tuple[int, int] | None = None,
                    storage: str = "f32",
                    block_hw: tuple[int, int] | None = None,
                    overlap: bool = False,
                    col_mode: str = "packed",
                    warn: bool = True) -> str:
    """Return the first backend in ``degradation_chain(backend)`` whose
    probe passes; raise immediately on a terminal probe failure.

    Emits :class:`BackendDegradedWarning` when the result differs from the
    request — callers (``utils.bench``, ``ConvolutionModel``) additionally
    stamp the returned name into their rows/attributes so the degradation
    is visible in artifacts, not only on stderr.

    ``overlap`` is clamped per walked tier (only the RDMA kernels have
    an overlapped form), so each probe compiles exactly the program the
    real launch would use on that tier — including the case where the
    OVERLAPPED RDMA program fails transiently and the walk lands on a
    serialized lower tier.
    """
    chain = degradation_chain(backend)
    last: BaseException | None = None
    for b in chain:
        try:
            probe_backend(mesh, filt, b, quantize=quantize, fuse=fuse,
                          boundary=boundary, tile=tile, storage=storage,
                          block_hw=block_hw, overlap=overlap,
                          col_mode=col_mode)
        except Exception as e:  # noqa: BLE001
            if classify(e) == TERMINAL:
                raise
            last = e
            continue
        if b != backend:
            if warn:
                warnings.warn(
                    f"backend {backend!r} degraded to {b!r} after transient "
                    f"failure: {last!r}",
                    BackendDegradedWarning, stacklevel=2,
                )
            if obs_metrics.enabled():
                obs_metrics.counter(
                    "pctpu_degrades_total",
                    "backend degradation walks that resolved a lower tier",
                    ("requested", "effective")).inc(
                    requested=backend, effective=b)
                obs_events.emit("degrade", requested=backend, effective=b,
                                cause=repr(last)[:200])
        _LAST_RESOLVED[backend] = b
        return b
    raise RetryExhausted(
        f"every backend in {chain} failed transiently; last: {last!r}"
    ) from last
