"""Pallas TPU stencil kernel (reference component C2 + C9, SURVEY.md §2).

The reference's hot loop is a per-pixel k×k multiply-add nest, multithreaded
with ``#pragma omp parallel for`` in the hybrid build.  Its TPU-native
equivalent is this Pallas kernel: the image block lives in HBM, a grid of
programs each DMAs one overlapping ``(TH+2r, TW+2r)`` window into VMEM
scratch, and the VPU (8×128 lanes — the OpenMP thread pool analog) computes
the same fixed-order shifted multiply-add the oracle defines, writing a
``(TH, TW)`` output tile.

Overlapping input windows cannot be expressed with blocked ``BlockSpec``
index maps (block start = index × block size), so the input uses
``memory_space=ANY`` and the kernel issues explicit ``make_async_copy``
windows — double-buffered across grid steps so the next tile's DMA overlaps
the current tile's compute (the reference's comm/compute-overlap idiom,
SURVEY.md §3.2, reborn on-chip).

Semantics contract: identical op order to ``ops.oracle.correlate_once`` /
``ops.conv.correlate_padded`` → float32 results are bit-identical, so the
kernel drops into the sharded step as a backend with no semantic change.
The two shorter tap forms (``tap_form``: rank-1 separable, box plus
sparse taps) leave that order only where every partial sum is exact, so
their bytes are the same.

The wrappers' own XLA work around a launch — the tile-alignment pad, the
crop back to the block, the band concatenation of a split launch — runs
under the named scope ``pctpu.kernel_io`` (``KERNEL_IO``), so a device
trace tells it from the kernels and from the halo (``step.op_scopes``).
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallel_convolution_tpu.ops.filters import Filter

KERNEL_IO = "pctpu.kernel_io"  # named scope of the wrappers' pad/crop/concat

# Default output-tile shapes: multiples of the f32 (8, 128) VMEM tile.
# Two defaults because Mosaic's scoped-VMEM stack scales differently per
# kernel form: the 2D tap loop keeps ~k² live (th, tw) f32 temporaries, so
# big tiles blow the 16 MB scoped limit (1024×512 f32 → 25.3 MB compile
# error on v5e); the separable form reuses one acc1/acc pair and takes
# large tiles fine.  Values chosen by scripts/tune_pallas.py on a real
# v5e (2026-07-29, tile threaded as an explicit static arg: 1024×512
# fuse32 123.8 Gpx/s vs 256×512 fuse32 116.8 — tile is a ~6% lever,
# fusion depth the ~4× one; 512×2048 fails Mosaic compile).
DEFAULT_TILE = (256, 512)
SEP_TILE = (1024, 512)

# Scoped-VMEM cap for the fused kernels.  The 2D tap loop keeps about k²
# live f32 temporaries of the level-0 region, which grows by 2r per fused
# level: edge5 at fuse 8 on the default tile needs 16.3 MiB, over Mosaic's
# 16 MiB default, and fails to compile for v5e (PR 21).  A v5e core has
# 128 MiB of VMEM; the cap only moves the compile-time check.
FUSED_VMEM_LIMIT = 64 * 2**20


def _default_tile(sep) -> tuple[int, int]:
    return SEP_TILE if sep is not None else DEFAULT_TILE


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublane(dtype) -> int:
    """Second-minor HBM/VMEM tile extent for a dtype (f32: 8, bf16: 16, u8: 32).

    Real Mosaic requires HBM DMA slice starts AND shapes aligned to the
    (sublane, 128) tiling — interpret mode does not enforce this, so every
    window extent below is rounded to it (first observed as a compile
    failure on silicon: "Slice shape along dimension 1 must be aligned to
    tiling (8), but is 258").
    """
    return 32 // jnp.dtype(dtype).itemsize


# Canonical implementation lives in utils.platform; re-exported here because
# kernel call sites (and the driver bench) historically import it from ops.
from parallel_convolution_tpu.utils.platform import on_tpu  # noqa: E402


def _to_f32(v):
    """Dtype-to-f32 inside a kernel; Mosaic (this jaxlib) has no direct
    u8↔f32 cast, so uint8 hops through int32 (exact for 0..255)."""
    if v.dtype == jnp.uint8:
        v = v.astype(jnp.int32)
    return v.astype(jnp.float32)


def _from_f32(v, dtype):
    """f32-to-storage-dtype inside a kernel (same Mosaic u8 hop)."""
    if jnp.dtype(dtype) == jnp.uint8:
        return v.astype(jnp.int32).astype(jnp.uint8)
    return v.astype(dtype)


def _sep_taps(filt: Filter, separable: bool):
    """Static (col_taps, row_taps) float tuples, or None if not requested
    or the filter has no exact rank-1 factorization."""
    if not separable:
        return None
    sep = filt.separable()
    if sep is None:
        return None
    col, row = sep
    return (tuple(float(t) for t in col), tuple(float(t) for t in row))


def _box_sparse_taps(filt: Filter, quantize: bool):
    """Static ``(c, residue)`` of the constant-plus-sparse form, or None.

    ``c`` is one tap value applied to the whole k×k window: a unit-weight
    separable box sum (k lane-shifted, then k sublane-shifted slices)
    times ``c``.  ``residue`` holds ``(dy, dx, tap - c)`` for every tap
    that differs from ``c``, one multiply-add each.  With ``c == 0`` the
    form is the direct loop minus its zero taps.  ``c`` is the value
    needing the fewest slices: the most frequent non-zero tap, or zero
    when it saves more (edge5: ``c = -1``, residue ``((2, 2, 25.0),)``;
    sharpen3: ``c = 0``, five taps).

    The form leaves the normative op order, so it is taken only where
    every partial sum is exact in f32 and any order gives the same bits:
    quantize mode (every level's input an integer in [0, 255]), taps that
    are integer multiples of 1/256, and 255·(k²|c| + Σ|residue|)·256 <
    2²⁴.  It must also take fewer slices than the k² of the direct loop.
    """
    if not (quantize and filt.dyadic):
        return None
    t = filt.taps.astype(np.float64)
    if not np.all(t * 256.0 == np.rint(t * 256.0)):
        return None
    k = filt.size
    vals = [float(v) for v in t.reshape(-1)]

    def slices(c):
        return (0 if c == 0.0 else 2 * k) + sum(v != c for v in vals)

    c = min(dict.fromkeys(vals), key=slices)  # ties: first in row-major
    residue = tuple((i // k, i % k, v - c) for i, v in enumerate(vals)
                    if v != c)
    l1 = k * k * abs(c) + sum(abs(rv) for _, _, rv in residue)
    if 255.0 * l1 * 256.0 >= 2.0**24 or slices(c) >= k * k:
        return None
    return c, residue


class TapForm(NamedTuple):
    """The static tap arithmetic of one kernel build.

    ``name`` is ``"direct"`` (``args``: the k² taps, row-major),
    ``"separable"`` (``args``: ``(col_taps, row_taps)``, see
    ``_sep_taps``) or ``"box_sparse"`` (``args``: ``(c, residue)``, see
    ``_box_sparse_taps``)."""

    name: str
    args: tuple


def rank1_exact(filt: Filter, quantize: bool) -> bool:
    """True where the rank-1 separable form gives the normative 2D
    order's bytes: quantize mode, a dyadic filter, and an exact rank-1
    factorization whose factors are integer multiples of 1/256 with
    255·L1(col)·L1(row)·2¹⁶ < 2²⁴, so every row- and column-pass
    partial sum is exact in f32 (DESIGN.md "Where a kernel may leave
    the normative order")."""
    if not (quantize and filt.dyadic):
        return False
    sep = filt.separable()
    if sep is None:
        return False
    col, row = (np.asarray(f, np.float64) * 256.0 for f in sep)
    if not (np.all(col == np.rint(col)) and np.all(row == np.rint(row))):
        return False
    return bool(255.0 * np.abs(col).sum() * np.abs(row).sum() < 2.0**24)


def tap_form(filt: Filter, separable: bool, quantize: bool) -> TapForm:
    """The tap form a kernel build runs: the rank-1 form where requested
    and exact, else the constant-plus-sparse form where exact and
    shorter, else the normative direct loop.  Decided from the taps and
    the quantize flag alone."""
    sep = _sep_taps(filt, separable)
    if sep is not None:
        return TapForm("separable", sep)
    box = _box_sparse_taps(filt, quantize)
    if box is not None:
        return TapForm("box_sparse", box)
    return TapForm("direct", tuple(float(t) for t in filt.taps.reshape(-1)))


def _correlate_window(win, form: TapForm, k, th, tw):
    """Stencil a (th+2r, tw+2r)+ f32-castable window down to (th, tw) f32.

    ``form`` (``tap_form``) picks the arithmetic.  ``separable`` is the
    rank-1 two-pass form — 2k MACs/px instead of k²
    (ops/conv.correlate_padded_separable's op order: full-height row
    pass, then column pass), bit-identical to the 2D order for dyadic
    factors over u8-range values.  ``box_sparse`` is ``c`` times a
    unit-weight box sum in the same two passes, then one multiply-add
    per residue tap, bit-identical in its exact regime.  ``direct`` is
    the normative row-major 2D multiply-add.
    """
    if form.name == "separable":
        colt, rowt = form.args
        acc1 = jnp.zeros((th + k - 1, tw), jnp.float32)
        for dx in range(k):
            acc1 = acc1 + jnp.float32(rowt[dx]) * _to_f32(
                win[: th + k - 1, dx : dx + tw])
        acc = jnp.zeros((th, tw), jnp.float32)
        for dy in range(k):
            acc = acc + jnp.float32(colt[dy]) * acc1[dy : dy + th, :]
        return acc
    acc = jnp.zeros((th, tw), jnp.float32)
    if form.name == "box_sparse":
        c, residue = form.args
        if c != 0.0:
            rows = _to_f32(win[: th + k - 1, :tw])
            for dx in range(1, k):
                rows = rows + _to_f32(win[: th + k - 1, dx : dx + tw])
            box = rows[:th, :]
            for dy in range(1, k):
                box = box + rows[dy : dy + th, :]
            acc = acc - box if c == -1.0 else acc + jnp.float32(c) * box
        for dy, dx, rv in residue:
            acc = acc + jnp.float32(rv) * _to_f32(
                win[dy : dy + th, dx : dx + tw])
        return acc
    idx = 0
    for dy in range(k):
        for dx in range(k):
            # f32 accumulation even for narrow storage (cast is VPU-free-ish).
            w = _to_f32(win[dy : dy + th, dx : dx + tw])
            acc = acc + jnp.float32(form.args[idx]) * w
            idx += 1
    return acc


def _prefetch_window(window_copy):
    """Double-buffered window pipeline shared by every gridded kernel.

    ``window_copy(cc, ii, jj, slot)`` must return the async copy of grid
    program (cc, ii, jj)'s window into scratch ``slot``.  Program n waits
    on the window it prefetched during program n-1 and starts program
    n+1's copy before computing (slot = parity of the linearized step);
    the first program primes the pipeline with its own window.  Returns
    the slot holding the current program's window.
    """
    c, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ni, nj = pl.num_programs(1), pl.num_programs(2)
    step = (c * ni + i) * nj + j
    slot = jax.lax.rem(step, 2)

    @pl.when(step == 0)
    def _():
        window_copy(c, i, j, slot).start()

    last = step == pl.num_programs(0) * ni * nj - 1

    @pl.when(jnp.logical_not(last))
    def _():
        nstep = step + 1
        nc = nstep // (ni * nj)
        nij = jax.lax.rem(nstep, ni * nj)
        window_copy(nc, nij // nj, jax.lax.rem(nij, nj), 1 - slot).start()

    window_copy(c, i, j, slot).wait()
    return slot


# 1.5 * 2**23: adding it to an f32 x with |x| < 2**22 forces the mantissa
# to integer precision (ulp = 1), i.e. the hardware rounds x to the nearest
# integer half-to-even; subtracting recovers that integer losslessly.  Two
# f32 adds == jnp.rint, bit for bit, on the whole quantize-mode range.
_MAGIC = 12582912.0


def _round_mode_for(taps, interpret) -> str:
    """Pick the rint implementation for a kernel build.

    Mosaic lowers ``jnp.rint`` to a multi-op sequence; replacing it with
    the two-add magic-number form measured **+15.6% on the u8 flagship /
    +12.6% bf16** on real v5e silicon, byte-identical
    (``evidence/round_mode_ab_r5.jsonl``, 2026-07-31).  Exactness needs
    |acc| < 2**22; every quantize-mode accumulator is bounded by
    255 * L1(taps), so filters with L1 < 2**21/255 (every shipped filter
    by orders of magnitude) qualify — anything larger falls back to
    ``rint``.

    Interpret-mode kernels run through XLA:CPU, whose algebraic
    simplifier FOLDS ``(x + C) - C`` to ``x`` (measured: the round
    disappears entirely) — there the adds are pinned with
    ``lax.optimization_barrier``.  Mosaic neither folds (the silicon
    byte-proof above) nor implements the barrier primitive, so compiled
    kernels use the bare form.

    Because "Mosaic never folds" has no semantic guarantee, the first
    compiled (non-interpret) quantized build in a process runs a one-time
    byte-guard — a tiny compiled kernel vs the NumPy oracle
    (``_compiled_magic_ok``).  On mismatch every compiled
    kernel falls back to ``rint`` with a loud warning, so CLI/library
    users on a future jax/Mosaic upgrade lose ~14% throughput, never
    correct bytes.
    """
    l1 = sum(abs(float(t)) for t in taps)
    if 255.0 * l1 >= 2.0**21:  # 2x safety margin under the 2**22 bound
        return "rint"
    if interpret:
        return "magic_barrier"
    return "magic" if _compiled_magic_ok() else "rint"


# Process-wide magic-round guard state: ``ok`` None = not yet probed;
# ``probing`` breaks the probe's own recursion into _round_mode_for (the
# probe kernel must build the very form under test); ``cause`` is
# "mismatch" once a fold was detected (a terminal condition for
# automation).  A probe that errors raises instead of setting a verdict.
_MAGIC_GUARD = {"ok": None, "probing": False, "cause": None}


def _probe_magic_round() -> bool:
    """Byte-compare ONE tiny compiled quantized kernel vs the NumPy oracle.

    Two chained quantized blur3 steps on a deterministic 16×128 grey
    plane — enough to catch a compiler that folds the two-add round (the
    rounding then vanishes and bytes diverge on the first store-back).
    Runs exactly once per process, on the first compiled quantized kernel
    build (sub-second next to any real workload's compile).
    """
    from parallel_convolution_tpu.ops import oracle
    from parallel_convolution_tpu.ops.filters import get_filter

    filt = get_filter("blur3")
    rng = np.random.default_rng(12)
    img = rng.integers(0, 256, size=(16, 128)).astype(np.uint8)
    want = oracle.run_serial_u8(img, filt, 2)
    # The selector — and hence this probe — is reached from INSIDE the
    # caller's jit trace (every quantized entry point is @jax.jit), where
    # np.asarray(got) would see a tracer and kill the probe on every
    # compiled build (reproduced: TracerArrayConversionError -> permanent
    # rint fallback).  jax trace state is thread-local, so a worker
    # thread starts from the eval trace — escaping the ambient trace
    # while keeping the probe's own inner jit/pallas compile intact
    # (ensure_compile_time_eval would instead disable the inner jit and
    # eval the pallas_call eagerly, which has no eval rules).
    import concurrent.futures

    def run():
        got = jnp.asarray(img[None], jnp.float32)
        for _ in range(2):
            got = correlate_shifted_pallas(got, filt, quantize=True,
                                           interpret=False)
        return np.asarray(got)

    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
        got = ex.submit(run).result()
    return bool(np.array_equal(got[0].astype(np.uint8), want))


def _compiled_magic_ok() -> bool:
    """One-time compiled-magic-round byte-guard, cached per process.

    True → compiled kernels keep the two-add magic round.  False (a byte
    mismatch: the compiler folds the round) → fall back to ``jnp.rint``
    with a RuntimeWarning: correctness must not hinge on an unverified
    compiler non-folding guarantee.  A probe that ERRORS proves nothing
    either way, so it raises: a compiled kernel that cannot run its own
    probe would not run the real workload either.
    """
    st = _MAGIC_GUARD
    if st["probing"]:
        return True  # the probe's own kernel builds the form under test
    if st["ok"] is None:
        st["probing"] = True
        try:
            ok = _probe_magic_round()
        except Exception as e:
            raise RuntimeError(
                f"magic-round byte-guard probe failed: {e!r}") from e
        finally:
            st["probing"] = False
        st["ok"] = ok
        if not ok:
            st["cause"] = "mismatch"
            warnings.warn(
                "magic-round byte-guard MISMATCH: a compiled quantized "
                "kernel diverged from the oracle (the compiler may now "
                "fold the two-add round) — falling back to jnp.rint "
                "for all compiled kernels this process",
                RuntimeWarning, stacklevel=3)
    return st["ok"]


def _quantize_acc(acc, convex, round_mode):
    """In-kernel u8 store-back on an f32 acc: rint, then clip — except the
    clip is elided for convex filters, where it is provably the identity
    (``Filter.convex``); results are bit-identical either way.

    ``round_mode`` selects the rint implementation (see
    ``_round_mode_for``); all three compute the same function."""
    if round_mode == "magic":
        acc = (acc + _MAGIC) - _MAGIC
    elif round_mode == "magic_barrier":
        acc = jax.lax.optimization_barrier(acc + _MAGIC) - _MAGIC
    else:
        acc = jnp.rint(acc)
    if not convex:
        acc = jnp.clip(acc, 0.0, 255.0)
    return acc


def _stencil_kernel(hbm_ref, out_ref, scratch, sems, *, form, k, r, th, tw,
                    ext_h, ext_w, quantize, convex, round_mode):
    """One grid program: DMA window c,i,j → VMEM, stencil it, emit tile.

    ``scratch`` holds two (ext_h, ext_w) slots — the (th+2r, tw+2r)
    stencil window rounded up to the HBM tiling (see ``_sublane``); the
    alignment rim is DMA'd but never read.
    """

    def window_copy(cc, ii, jj, slot):
        return pltpu.make_async_copy(
            hbm_ref.at[cc, pl.ds(ii * th, ext_h), pl.ds(jj * tw, ext_w)],
            scratch.at[slot],
            sems.at[slot],
        )

    slot = _prefetch_window(window_copy)

    acc = _correlate_window(scratch[slot], form, k, th, tw)
    if quantize:
        # Fused u8 store-back: saves one full HBM round trip per iteration
        # vs quantizing in a separate XLA fusion after the kernel.
        acc = _quantize_acc(acc, convex, round_mode)
    out_ref[0] = _from_f32(acc, out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("filt", "tile", "interpret", "quantize", "out_dtype",
                     "separable"),
)
def correlate_padded_pallas(
    padded: jnp.ndarray,
    filt: Filter,
    tile: tuple[int, int] | None = None,
    interpret: bool | None = None,
    quantize: bool = False,
    out_dtype=None,
    separable: bool = False,
) -> jnp.ndarray:
    """Stencil an already-padded (C, H+2r, W+2r) block → (C, H, W).

    Drop-in replacement for ``ops.conv.correlate_padded`` (same normative op
    order).  ``interpret=None`` auto-selects the Pallas interpreter off-TPU
    so the kernel is testable on the forced-CPU mesh.

    ``quantize=True`` fuses the u8 store-back into the kernel;
    ``out_dtype`` (default: input dtype if quantizing, else f32) enables
    bf16 storage — quantized values are exact integers ≤ 255, which bf16
    represents exactly, so bf16 carries halve HBM/ICI traffic with no
    semantic change.

    ``separable=True`` uses the rank-1 two-pass form when the filter has an
    exact dyadic factorization (2k MACs/px instead of k² — the VPU-bound
    fused path's main cost); silently falls back to 2D otherwise.  Same
    exactness contract as the XLA 'separable' backend: bit-identical in
    quantize mode, a rounding-order change in float mode.
    """
    if interpret is None:
        interpret = not on_tpu()
    if out_dtype is None:
        out_dtype = padded.dtype if quantize else jnp.float32
    sep = _sep_taps(filt, separable)
    if tile is None:
        tile = _default_tile(sep)
    r = filt.radius
    k = filt.size
    C, Hp, Wp = padded.shape
    H, W = Hp - 2 * r, Wp - 2 * r

    sub = _sublane(padded.dtype)
    th, tw, gh, gw = fused_tile_grid((H, W), padded.dtype, tile, sep)
    # Tile-aligned DMA window: starts i*th / j*tw are aligned because
    # th % sub == 0 and tw % 128 == 0; extents rounded up from th+2r.
    ext_h, ext_w = th + _round_up(2 * r, sub), tw + _round_up(2 * r, 128)
    # Round the compute domain up to whole tiles plus the alignment rim;
    # the rim is garbage-over-zeros, never read, and sliced off below.
    eh = (gh - 1) * th + ext_h - Hp
    ew = (gw - 1) * tw + ext_w - Wp
    if eh > 0 or ew > 0:
        with jax.named_scope(KERNEL_IO):
            padded = jnp.pad(padded,
                             ((0, 0), (0, max(eh, 0)), (0, max(ew, 0))))

    taps = tuple(float(t) for t in filt.taps.reshape(-1))
    kernel = functools.partial(
        _stencil_kernel, form=tap_form(filt, separable, quantize),
        k=k, r=r, th=th, tw=tw, ext_h=ext_h, ext_w=ext_w, quantize=quantize,
        convex=filt.convex,
        round_mode=(_round_mode_for(taps, interpret) if quantize
                    else "rint"),  # unused when not quantizing: skip the
                                   # compiled-probe guard a float build
                                   # would otherwise pay for nothing
    )
    # Propagate varying-mesh-axes so the kernel composes under shard_map
    # (check_vma needs the out type to declare what it varies over).
    vma = jax.typeof(padded).vma
    out = pl.pallas_call(
        kernel,
        grid=(C, gh, gw),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, th, tw), lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, gh * th, gw * tw), out_dtype,
                                       vma=vma),
        scratch_shapes=[
            pltpu.VMEM((2, ext_h, ext_w), padded.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(padded)
    with jax.named_scope(KERNEL_IO):
        return out[:, :H, :W]


def correlate_shifted_pallas(x: jnp.ndarray, filt: Filter, **kw) -> jnp.ndarray:
    """Zero-padded stencil step on unpadded (C, H, W) via the Pallas kernel."""
    r = filt.radius
    return correlate_padded_pallas(
        jnp.pad(x, ((0, 0), (r, r), (r, r))), filt, **kw
    )


# ---------------------------------------------------------------------------
# Temporal fusion kernel: T stencil iterations per HBM round trip.
# ---------------------------------------------------------------------------


def fused_tile_grid(valid_hw, dtype, tile, sep=None):
    """Static (th, tw, gh, gw) the fused launch uses for a block of valid
    extent ``valid_hw``: the requested tile rounded to the dtype's
    (sublane, 128) tiling and clamped to the block, and the resulting
    tile-grid shape.  Shared between ``fused_iterate_pallas`` and the
    geometry-prediction tooling (scripts/profile_flagship.py) so a
    prediction can never drift from the real launch."""
    h, w = valid_hw
    if tile is None:
        tile = _default_tile(sep)
    sub = _sublane(dtype)
    th = min(_round_up(tile[0], sub), _round_up(h, sub))
    tw = min(_round_up(tile[1], 128), _round_up(w, 128))
    return th, tw, -(-h // th), -(-w // tw)


def _iterate_levels(cur, *, form, k, r, T, out_hw, quantize, convex,
                    round_mode, rows0=None, cols0=None, valid_hw=None):
    """T level-shrinking stencil levels: (oh + 2rT, ow + 2rT) f32 → (oh, ow).

    The single source of the temporal-fusion compute shape, shared by the
    ppermute fused kernel (``_fused_kernel``) and both RDMA fuse>1 kernels
    (``ops/pallas_rdma.py``) so the quantize path — magic round included —
    and the tap chain (``form``: direct, separable or box_sparse) are
    threaded identically everywhere.

    Per level the window shrinks by r; ``rows0``/``cols0`` are the hoisted
    GLOBAL-coordinate iotas of the level-0 window ((w0h, 1) / (1, w0w));
    when present, out-of-``valid_hw`` positions of every level are
    re-zeroed with the cheap rank-1 broadcast multiplies — exactly the
    oracle's ghost ring at every intermediate level.  ``None`` statically
    drops that mask axis (the periodic torus).
    Every level-0 value must already be finite (the caller's select tier)
    — a multiplicative mask would leak NaN otherwise.
    """
    oh, ow = out_hw
    H, W = valid_hw if valid_hw is not None else (None, None)
    for s in range(1, T + 1):
        ch, cw = oh + 2 * r * (T - s), ow + 2 * r * (T - s)
        acc = _correlate_window(cur, form, k, ch, cw)
        if quantize:
            acc = _quantize_acc(acc, convex, round_mode)
        # Level-s window starts r*s deeper; slice the hoisted iotas.
        if rows0 is not None:
            rows = rows0[r * s : r * s + ch, :]
            acc = acc * ((rows >= 0) & (rows < H)).astype(jnp.float32)
        if cols0 is not None:
            cols = cols0[:, r * s : r * s + cw]
            acc = acc * ((cols >= 0) & (cols < W)).astype(jnp.float32)
        cur = acc
    return cur


def _fused_kernel(off_ref, hbm_ref, out_ref, scratch, sems, *,
                  form, k, r, T, th, tw, ext_h, ext_w, valid_hw,
                  quantize, convex, round_mode):
    """T in-VMEM stencil levels on one (th + 2rT, tw + 2rT) window.

    The window shrinks by r per level; after each level, positions outside
    the valid global image are re-zeroed (the oracle's ghost ring at every
    intermediate level) using the shard's global offset from SMEM.  One HBM
    read + one HBM write buy T iterations — the bandwidth analog of the
    fuse=T collective saving.
    """
    i, j = pl.program_id(1), pl.program_id(2)

    def window_copy(cc, ii, jj, slot):
        return pltpu.make_async_copy(
            hbm_ref.at[cc, pl.ds(ii * th, ext_h), pl.ds(jj * tw, ext_w)],
            scratch.at[slot],
            sems.at[slot],
        )

    slot = _prefetch_window(window_copy)

    # Global coords of the window's top-left at level 0.  The scratch slot
    # is the (th+2rT, tw+2rT) stencil window plus an alignment rim (bottom/
    # right) that is DMA'd but dropped here.
    row0 = off_ref[0] - r * T + i * th
    col0 = off_ref[1] - r * T + j * tw
    cur = _to_f32(scratch[slot][: th + 2 * r * T, : tw + 2 * r * T])
    rows0 = cols0 = None
    if valid_hw is not None:
        # Ghost-ring masking in two tiers (none at all on the periodic
        # torus):
        #
        # 1. ONE select on the level-0 window: out-of-image positions
        #    (halo beyond the image edge, pad rim) are forced to exactly 0,
        #    so any non-finite garbage the DMA may have carried dies here
        #    (a multiplicative mask alone would leak it: 0 * NaN = NaN).
        # 2. Per level, the cheap rank-1 form: the out-of-image region of
        #    any level's window is a row band ⊗ column band, so re-zeroing
        #    is one broadcast multiply per axis (~1 VPU op/px each).
        #    Exact because tier 1 guarantees every intermediate is finite.
        #    Measured on v5e: per-level 2D select instead cost ~20%
        #    throughput at fuse=16 AND ~2× Mosaic compile time per
        #    doubling of T.
        #
        # Branching around the mask for interior tiles is NOT worth it:
        # one lax.cond per program measured 40% slower on Mosaic than
        # unconditional masking (it stalls the DMA/compute pipeline), and
        # a static unmasked-interior launch split measured 1.004x on v5e
        # (DESIGN.md): the multiplies are free on the VPU.
        H, W = valid_hw
        w0h, w0w = th + 2 * r * T, tw + 2 * r * T
        rows0 = row0 + jax.lax.broadcasted_iota(jnp.int32, (w0h, 1), 0)
        ok0 = (rows0 >= 0) & (rows0 < H)
        cols0 = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, w0w), 1)
        ok0 = ok0 & ((cols0 >= 0) & (cols0 < W))
        cur = jnp.where(ok0, cur, 0.0)
    cur = _iterate_levels(cur, form=form, k=k, r=r, T=T,
                          out_hw=(th, tw), quantize=quantize, convex=convex,
                          round_mode=round_mode, rows0=rows0, cols0=cols0,
                          valid_hw=valid_hw)
    out_ref[0] = _from_f32(cur, out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("filt", "T", "valid_hw", "tile", "interpret",
                     "quantize", "out_dtype", "separable"),
)
def fused_iterate_pallas(
    padded: jnp.ndarray,
    offsets: jnp.ndarray,
    filt: Filter,
    T: int,
    valid_hw: tuple[int, int],
    tile: tuple[int, int] | None = None,
    interpret: bool | None = None,
    quantize: bool = True,
    out_dtype=None,
    separable: bool = False,
) -> jnp.ndarray:
    """T stencil iterations of a deep-padded (C, h+2rT, w+2rT) block.

    ``padded`` comes from a depth-``r*T`` halo exchange; ``offsets`` is a
    (2,) int32 array holding the block's global (row0, col0) — dynamic under
    shard_map — used for per-level ghost-ring masking against ``valid_hw``.
    Bit-exact with T applications of the one-step kernel (same op order,
    intermediates at full f32 in VMEM).
    """
    if interpret is None:
        interpret = not on_tpu()
    if out_dtype is None:
        out_dtype = padded.dtype
    sep = _sep_taps(filt, separable)
    if tile is None:
        tile = _default_tile(sep)
    r, k = filt.radius, filt.size
    C, Hp, Wp = padded.shape
    h, w = Hp - 2 * r * T, Wp - 2 * r * T

    sub = _sublane(padded.dtype)
    th, tw, gh, gw = fused_tile_grid((h, w), padded.dtype, tile, sep)
    ext_h = th + _round_up(2 * r * T, sub)
    ext_w = tw + _round_up(2 * r * T, 128)
    eh = (gh - 1) * th + ext_h - Hp
    ew = (gw - 1) * tw + ext_w - Wp
    if eh > 0 or ew > 0:
        with jax.named_scope(KERNEL_IO):
            padded = jnp.pad(padded,
                             ((0, 0), (0, max(eh, 0)), (0, max(ew, 0))))

    taps = tuple(float(t) for t in filt.taps.reshape(-1))
    kernel = functools.partial(
        _fused_kernel, form=tap_form(filt, separable, quantize),
        k=k, r=r, T=T, th=th, tw=tw, ext_h=ext_h, ext_w=ext_w,
        valid_hw=tuple(valid_hw) if valid_hw is not None else None,
        quantize=quantize, convex=filt.convex,
        round_mode=(_round_mode_for(taps, interpret) if quantize
                    else "rint"),  # dead when not quantizing
    )
    out = pl.pallas_call(
        kernel,
        grid=(C, gh, gw),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, th, tw), lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, gh * th, gw * tw), out_dtype,
                                       vma=jax.typeof(padded).vma),
        scratch_shapes=[
            pltpu.VMEM((2, ext_h, ext_w), padded.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(offsets.astype(jnp.int32), padded)
    with jax.named_scope(KERNEL_IO):
        return out[:, :h, :w]
