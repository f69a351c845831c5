"""Kernel-form registry: dispatch is a table, not an if-ladder.

Until round 15 ``parallel/step.py`` selected its per-backend program by
string comparison (``backend == "pallas_rdma"`` / the
``_correlate_for_backend`` ladder), and every capability question —
"does this backend have an overlapped halo pipeline?" — was answered by
repeating the same comparison at each call site (three verbatim clamps
in step.py alone).  New stencil families (the multigrid transfer
operators this round, 3D forms later) would each have grown the ladder.

This module is the replacement: a process-global registry of
:class:`KernelForm` records keyed by ``(rank, name, boundary)``:

* ``rank`` — spatial rank of the stencil (2 today; a (D, H, W) volume
  path registers rank 3 without touching dispatch);
* ``name`` — the program family: a backend name from the canonical
  ``BACKENDS`` registry for smoothers, or an operator name for other
  stencil forms (``restrict_fw``, ``prolong_bilinear``);
* ``boundary`` — one key per supported boundary, so an unsupported
  (form, boundary) combination fails at *resolution*, loudly, instead
  of deep inside a trace.

Each form carries its ``stencil_form`` class (``smooth`` | ``restrict``
| ``prolong``), a per-form **capability bit** for the overlapped halo
pipeline (``overlap_capable`` — the one place that knowledge lives; the
clamps that were duplicated across step/bench/engine/degrade now call
:func:`clamp_overlap`), and its ``build`` callable — the factory that
returns the per-block step function ``parallel/step._build_*`` compiles
into shard_map programs.

Key contract (pinned by ``tests/test_multigrid.py``): the ``smooth``
key set is exactly ``{(2, b, bd) for b in BACKENDS for bd in
BOUNDARIES}`` — the old ladder, no more, no less; transfer operators
and future forms extend the registry under their own ``stencil_form``
without widening the smoother set.

jax-free at import: forms are *declared* here and *registered* by the
modules that own their implementations (``parallel/step.py`` registers
the six smoother families at import; ``solvers/transfer.py`` the
multigrid transfer operators).  :func:`resolve` lazily imports the
default providers on a miss, so a caller that asks before importing
step still gets an answer.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

__all__ = ["KernelForm", "block_multiple", "clamp_overlap",
           "overlap_capable", "persistent_capable", "register",
           "registered_keys", "resolve"]

# The stencil-form vocabulary (closed: dispatch code switches on it).
# "physics" (round 23) classes the time-dependent rank-3 forms (wave,
# Gray–Scott): they iterate like smoothers but are NOT convergence
# smoothers — converge admission keys off the class, not the name.
STENCIL_FORMS = ("smooth", "restrict", "prolong", "physics")


@dataclasses.dataclass(frozen=True)
class KernelForm:
    """One registered stencil program family.

    ``build`` is the step factory, with the signature its registering
    module calls it by (``step._make_block_step`` for smoothers).  The
    registry stores and resolves; it never calls.
    """

    name: str
    rank: int = 2
    stencil_form: str = "smooth"
    boundaries: tuple[str, ...] = ("zero", "periodic")
    overlap_capable: bool = False
    # Persistent halo channels (parallel.channels): the form binds its
    # exchange descriptors once per identity and reuses them across
    # fused iterations / converge chunks / V-cycle levels.  The one
    # place that knowledge lives (round 16) — the cost model's zeroed
    # setup term and the col_mode resolution both key off it.
    persistent_capable: bool = False
    # Blocks aligned to the storage dtype's HBM tiling, (sublane, 128):
    # the form moves whole tiles by DMA, and Mosaic refuses a slice of
    # any other shape.  The dispatch layer pads the image so each
    # device's block is such a multiple (:func:`block_multiple`).
    tile_aligned_blocks: bool = False
    build: Callable | None = None

    def __post_init__(self) -> None:
        if self.stencil_form not in STENCIL_FORMS:
            raise ValueError(
                f"stencil_form must be one of {STENCIL_FORMS}, got "
                f"{self.stencil_form!r}")
        if not self.boundaries:
            raise ValueError(f"form {self.name!r} supports no boundary")


_FORMS: dict[tuple[int, str, str], KernelForm] = {}


def register(form: KernelForm) -> KernelForm:
    """Install ``form`` under one key per supported boundary.

    Re-registering the same (name, rank) with a different shape — or a
    different ``build`` provider — raises: two modules silently fighting
    over a key would make dispatch depend on import order.  Idempotent
    re-registration (module reload) is allowed when the declared
    capabilities match and ``build`` resolves to the same provider
    (compared by module/qualname, not object identity, so a reload's
    fresh function objects still count as the same provider).
    """
    for bd in form.boundaries:
        key = (form.rank, form.name, bd)
        old = _FORMS.get(key)
        if old is not None and (
                old.stencil_form != form.stencil_form
                or old.overlap_capable != form.overlap_capable
                or old.persistent_capable != form.persistent_capable
                or old.tile_aligned_blocks != form.tile_aligned_blocks
                or old.boundaries != form.boundaries
                or _build_id(old.build) != _build_id(form.build)):
            raise ValueError(
                f"kernel form {key} already registered with different "
                f"capabilities ({old.stencil_form}/"
                f"overlap={old.overlap_capable}) or a different build "
                f"provider")
        _FORMS[key] = form
    return form


def _build_id(build) -> tuple:
    """Stable identity of a build callable: the underlying function's
    (module, qualname) plus any ``functools.partial`` args."""
    if build is None:
        return (None,)
    f = getattr(build, "func", build)
    return (getattr(f, "__module__", None),
            getattr(f, "__qualname__", None),
            tuple(getattr(build, "args", ())))


def _ensure_default_forms() -> None:
    """Import the default providers (idempotent) so resolution works in
    any import order — the registry is jax-free, the implementations
    are not, so they land lazily on the first miss."""
    from parallel_convolution_tpu.parallel import step  # noqa: F401
    from parallel_convolution_tpu.solvers import transfer  # noqa: F401
    from parallel_convolution_tpu.volumes import forms  # noqa: F401


def resolve(rank: int, name: str, boundary: str) -> KernelForm:
    """The form dispatch compiles for ``(rank, name, boundary)``.

    Raises ``ValueError`` (the service's typed-``invalid`` class) naming
    the available keys when nothing is registered — the error surface
    the old ladder's ``unknown backend`` branch provided, now covering
    every stencil form.
    """
    key = (int(rank), str(name), str(boundary))
    form = _FORMS.get(key)
    if form is None:
        _ensure_default_forms()
        form = _FORMS.get(key)
    if form is None:
        names = sorted({k[1] for k in _FORMS if k[0] == key[0]})
        raise ValueError(
            f"no kernel form registered for rank={key[0]} name={key[1]!r} "
            f"boundary={key[2]!r}; registered rank-{key[0]} forms: {names}")
    return form


def registered_keys(stencil_form: str | None = None) -> frozenset:
    """The registered ``(rank, name, boundary)`` key set, optionally
    filtered by stencil form — the pinned-test surface."""
    _ensure_default_forms()
    return frozenset(k for k, f in _FORMS.items()
                     if stencil_form is None
                     or f.stencil_form == stencil_form)


def overlap_capable(name: str, rank: int = 2) -> bool:
    """Whether ``name`` has an interior-first overlapped halo pipeline —
    the per-form capability bit.  Unknown names are simply not capable
    (the degrade walk may probe names mid-registration)."""
    _ensure_default_forms()
    for bd in ("zero", "periodic"):
        form = _FORMS.get((int(rank), str(name), bd))
        if form is not None:
            return form.overlap_capable
    return False


def persistent_capable(name: str, rank: int = 2) -> bool:
    """Whether ``name`` binds persistent halo channels — the per-form
    capability bit (round 16).  Unknown names are not capable."""
    _ensure_default_forms()
    for bd in ("zero", "periodic"):
        form = _FORMS.get((int(rank), str(name), bd))
        if form is not None:
            return form.persistent_capable
    return False


def block_multiple(name: str, storage: str, rank: int = 2) -> tuple[int, int]:
    """The (rows, cols) multiple a per-device block of ``name`` must be
    at ``storage``: the dtype's (sublane, 128) tiling for a form
    registered ``tile_aligned_blocks``, else (1, 1).  Unknown names
    (``auto`` before resolution) need no alignment."""
    from parallel_convolution_tpu.tuning.costmodel import LANE, SUBLANE

    _ensure_default_forms()
    for bd in ("zero", "periodic"):
        form = _FORMS.get((int(rank), str(name), bd))
        if form is not None and form.tile_aligned_blocks:
            return SUBLANE[storage], LANE
    return 1, 1


def clamp_overlap(overlap, name: str, rank: int = 2) -> bool:
    """The one overlap-legality clamp: a resolved/degraded backend keeps
    ``overlap=True`` only if its registered form is overlap-capable.

    Replaces the three verbatim ``overlap and backend == "pallas_rdma"``
    clamps in step.py (and their copies in bench/engine/pipeline): a new
    overlap-capable form inherits legality by *registering* the bit, and
    the multigrid smoother inherits it for free because it dispatches
    through the same names.
    """
    return bool(overlap) and overlap_capable(name, rank)
