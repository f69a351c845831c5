"""Persistent, partitioned halo channels + the packed/strided column A/B.

Round 16's three claims, each pinned here:

* **Persistent channels** (``parallel.channels``): descriptor plans are
  bound ONCE per exchange identity and reused by every trace that
  shares it — fused iteration chunks, converge chunks, V-cycle levels —
  with the build/hit counters as assertable evidence, and the 1x1
  grid's plan holding NO channels at all (the static-elision contract:
  the degenerate program is the serialized local program verbatim,
  independent of ``col_mode``/``partitioned``, pinned at the LOWERED
  PROGRAM level).

* **Partitioned completion**: a region/window waits on exactly the slab
  channels whose inbound write rectangle its read region overlaps — no
  missed wait (a race), no extra wait (lost overlap).  The wait-set
  derivations the kernels consume (``overlap_region_slabs``,
  ``tiled_window_hazards``) are property-tested against independent
  interval intersection over the ISSUE's grid/boundary/fuse matrix,
  and full-protocol byte proofs run under the DMA-faithful interpreter
  (skip-with-cause on stock jax, like tests/test_rdma.py).

* **Packed-vs-strided column transport**: both modes byte-identical
  through kernels and dispatch, the cost model's split setup/transfer
  exchange terms and the new constants drift-guarded, and the resolved
  ``col_mode`` threaded plan→search→bench rows→EngineKey→responses.
"""

import numpy as np
import pytest

import jax

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import (
    channels, kernels as kernel_forms, mesh as mesh_lib, step,
)
from parallel_convolution_tpu.utils import imageio



def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]],
                                   shape)


def _run(img, filt, iters, mesh_shape, *, boundary="zero", fuse=1,
         overlap=False, col_mode="strided", partitioned=True,
         tiled=None, tile=None):
    """Chained fused_rdma_step invocations straight at the kernel (the
    dispatch clamps deliberately bypassed: this file proves PROGRAM
    bytes per (col_mode, partitioned, overlap) variant)."""
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES, padded_extent

    mesh = _mesh(mesh_shape)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    H, W = img.shape[:2]
    valid_hw = None if boundary == "periodic" else (H, W)
    if valid_hw is not None:
        # shard_map needs block multiples: pad as parallel/step.py's
        # _prepare does, and crop back to the valid extent below.
        x = np.pad(x, ((0, 0), (0, padded_extent(H, mesh_shape[0]) - H),
                       (0, padded_extent(W, mesh_shape[1]) - W)))
    block_hw = (x.shape[1] // mesh_shape[0], x.shape[2] // mesh_shape[1])
    # One level per launch leaves the pad rim unmasked in the kernel; the
    # step re-zeroes it outside (parallel/step.py), and so does this.
    rim_mask = valid_hw is not None and fuse == 1 and (H, W) != (
        x.shape[1], x.shape[2])
    n = iters // fuse

    def body(v):
        import jax.lax as lax

        def one(_, cur):
            p = pallas_rdma.fused_rdma_step(
                cur, filt, mesh_shape, boundary, quantize=True,
                tiled=tiled, tile=tile, fuse=fuse, valid_hw=valid_hw,
                overlap=overlap, col_mode=col_mode,
                partitioned=partitioned)
            if rim_mask:
                p = p * step._valid_mask(valid_hw, block_hw).astype(p.dtype)
            return p
        return lax.fori_loop(0, n, one, v)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    return np.asarray(out)[0, :H, :W].astype(np.uint8)


# ---------------------------------------------------------------------------
# The channel-plan layer: identity, caching, static elision.
# ---------------------------------------------------------------------------


def _key(**kw):
    base = dict(grid=(2, 4), block_hw=(16, 32), radius=1, fuse=2,
                dtype="float32", boundary="zero", kernel="monolithic",
                col_mode="strided")
    base.update(kw)
    return channels.ChannelKey(**base)


def test_channel_plan_identity_and_reuse():
    channels.reset()
    p1 = channels.plan_for(_key())
    p2 = channels.plan_for(_key())
    assert p1 is p2  # the SAME bound object, not an equal rebuild
    assert channels.stats() == {"builds": 1, "hits": 1}
    channels.plan_for(_key(fuse=4))          # new identity
    channels.plan_for(_key(col_mode="packed"))
    channels.plan_for(_key(kernel="tiled"))
    assert channels.stats()["builds"] == 4
    channels.reset()
    assert channels.stats() == {"builds": 0, "hits": 0}


def test_channel_plan_rejects_unresolved_auto():
    with pytest.raises(ValueError, match="resolved, never 'auto'"):
        _key(col_mode="auto")


def test_monolithic_slab_geometry():
    """The plan's slab table IS halo.halo_exchange's slab math: row
    slabs d-deep at interior columns, column slabs at FULL padded
    height (two-hop corners), SPMD-symmetric src/dst pairing."""
    plan = channels.plan_for(_key())
    h, w, d = 16, 32, 2
    up = plan.slab("up")
    assert (up.src_rows, up.src_cols) == ((d, 2 * d), (d, d + w))
    assert (up.dst_rows, up.dst_cols) == ((h + d, h + 2 * d), (d, d + w))
    assert up.nbr == (-1, 0) and up.sem == channels.SEM_UP
    left = plan.slab("left")
    assert left.src_rows == (0, h + 2 * d)
    assert left.src_cols == (d, 2 * d)
    assert left.dst_cols == (w + d, w + 2 * d)
    assert left.nbr == (0, -1)
    # Strided plans never stage; packed plans stage only with a remote
    # column partner.
    assert not plan.packed_cols
    assert channels.plan_for(_key(col_mode="packed")).packed_cols


def test_degenerate_plan_has_no_channels():
    """1x1 grid: NO slabs, NO staging — the machinery statically elides
    (the ISSUE's degenerate-1x1 satellite)."""
    for cm in ("packed", "strided"):
        plan = channels.plan_for(_key(grid=(1, 1), col_mode=cm))
        assert plan.slabs() == ()
        assert not plan.packed_cols
        assert not plan.row_wrap and not plan.col_wrap
    # Periodic self-wrap axes are wraps, not channels.
    plan = channels.plan_for(_key(grid=(1, 1), boundary="periodic",
                                  block_hw=(16, 32)))
    assert plan.slabs() == () and plan.row_wrap and plan.col_wrap


def test_registry_persistent_bit_and_costmodel_mirror():
    from parallel_convolution_tpu.tuning import costmodel
    from parallel_convolution_tpu.utils.config import BACKENDS

    for b in BACKENDS:
        assert kernel_forms.persistent_capable(b) == (
            b in costmodel.PERSISTENT_BACKENDS)
    assert kernel_forms.persistent_capable("pallas_rdma")
    assert not kernel_forms.persistent_capable("no_such_form")


def test_degenerate_static_elision_lowered_identical():
    """On a 1x1 grid both column transports (and both completion
    ledgers) must compile the IDENTICAL program — pinned at the lowered
    text level, the 'verbatim serialized program' claim."""
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES

    filt = filters.get_filter("blur3")
    mesh = _mesh((1, 1))
    x = np.zeros((1, 24, 40), np.float32)

    def lowered(col_mode, partitioned, overlap=False):
        def body(v):
            return pallas_rdma.fused_rdma_step(
                v, filt, (1, 1), "zero", quantize=True, fuse=2,
                valid_hw=(24, 40), overlap=overlap, col_mode=col_mode,
                partitioned=partitioned)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(None, *AXES),
            out_specs=P(None, *AXES), check_vma=False)).lower(x).as_text()

    base = lowered("strided", True)
    assert lowered("packed", True) == base
    assert lowered("strided", False) == base
    # Under overlap the region-split program differs from serialized (as
    # before r16), but the column transport still elides completely:
    # packed and strided lower to the identical overlapped program.
    ov = lowered("strided", True, overlap=True)
    assert lowered("packed", True, overlap=True) == ov


# ---------------------------------------------------------------------------
# Partitioned completion: wait-set soundness (the property tests).
# ---------------------------------------------------------------------------


def _rects_overlap(a, b):
    (ar0, ar1, ac0, ac1), (br0, br1, bc0, bc1) = a, b
    return ar0 < br1 and br0 < ar1 and ac0 < bc1 and bc0 < ac1


@pytest.mark.parametrize("h,w,d", [(32, 48, 2), (8, 8, 4), (5, 40, 2),
                                   (3, 3, 2), (16, 4, 1), (64, 64, 8),
                                   (7, 64, 3)])
def test_monolithic_region_wait_sets_exact(h, w, d):
    """Every region's wait set == exactly the slab channels whose
    inbound write rect its pad-coordinate read window overlaps — no
    missed wait (a race with an in-flight DMA), no extra wait (lost
    overlap).  Independent brute-force interval check, including the
    degenerate all-rim geometries."""
    from parallel_convolution_tpu.ops.pallas_rdma import (
        overlap_region_slabs, overlap_regions,
    )

    writes = {
        "up": (0, d, d, d + w),
        "down": (h + d, h + 2 * d, d, d + w),
        "left": (0, h + 2 * d, 0, d),
        "right": (0, h + 2 * d, w + d, w + 2 * d),
    }
    regions = overlap_region_slabs(h, w, d)
    # Same partition as overlap_regions — every output pixel once.
    cover = np.zeros((h, w), np.int32)
    for _label, (r0, r1, c0, c1), _waits in regions:
        cover[r0:r1, c0:c1] += 1
    np.testing.assert_array_equal(cover, np.ones((h, w), np.int32))
    interior, _rb, _cb = overlap_regions(h, w, d)
    for label, rect, waits in regions:
        read = (rect[0], rect[1] + 2 * d, rect[2], rect[3] + 2 * d)
        want = frozenset(name for name, wr in writes.items()
                         if _rects_overlap(read, wr))
        assert waits == want, (label, rect, waits, want)
        if label == "interior":
            assert waits == frozenset()
    # Schedule order: interior first, then bands (the compute order the
    # kernel walks).
    assert [lb for lb, _, _ in regions][:len(interior)] == (
        ["interior"] * len(interior))


@pytest.mark.parametrize("grid", [(2, 4), (2, 2), (1, 8), (4, 1)])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("fuse", [1, 2, 4])
def test_tiled_window_wait_sets_exact(grid, boundary, fuse):
    """The ISSUE's property matrix: for every window of a multi-window
    tiled launch, ``tiled_window_hazards`` == brute-force intersection
    of the window's (ext_h, ext_w) read region with each direction's
    transferred band — and every live band is retired by SOME window
    (the semaphore-hygiene half of soundness)."""
    from parallel_convolution_tpu.ops.pallas_rdma import (
        tiled_window_hazards,
    )

    sub_v, lane = 8, 128
    h, w = 32, 256            # per-device block: multi-window grid
    th, tw = 8, 128
    d = 1 * fuse
    assert d <= min(sub_v, lane)
    gh, gw = -(-h // th), -(-w // tw)
    ext_h, ext_w = th + 2 * sub_v, tw + 2 * lane
    bands = {
        "up": (0, sub_v, lane, lane + w),
        "down": (h + sub_v, h + 2 * sub_v, lane, lane + w),
        "left": (0, h + 2 * sub_v, lane, 2 * lane),
        "right": (0, h + 2 * sub_v, w, w + lane),
    }
    # Band WRITE rects (dst side): left ghost lands at cols [0, lane),
    # right ghost at [w+lane, w+2lane) — the read-hazard rects.
    dst = {
        "up": (0, sub_v, lane, lane + w),
        "down": (h + sub_v, h + 2 * sub_v, lane, lane + w),
        "left": (0, h + 2 * sub_v, 0, lane),
        "right": (0, h + 2 * sub_v, w + lane, w + 2 * lane),
    }
    covered = {k: False for k in dst}
    for wi in range(gh):
        for wj in range(gw):
            hz = tiled_window_hazards(wi, wj, th=th, tw=tw, h=h, w=w,
                                      sub_v=sub_v, lane=lane)
            read = (wi * th, wi * th + ext_h, wj * tw, wj * tw + ext_w)
            for name, rect in dst.items():
                want = _rects_overlap(read, rect)
                assert bool(hz[name]) == want, (wi, wj, name)
                covered[name] = covered[name] or want
    # Every direction's inbound band is touched by at least one window:
    # its semaphores provably retire inside the grid (no hang, no leak)
    # — for ANY of the matrix's grids/boundaries, since existence only
    # prunes waits at runtime, never adds them.
    assert all(covered.values()), covered
    assert grid and boundary  # matrix parameters exercise the claim set


# ---------------------------------------------------------------------------
# Byte proofs: degenerate grids on any jax; full protocol under the
# faithful interpreter (skip-with-cause on stock jax).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundary", ["zero", "periodic"])
@pytest.mark.parametrize("col_mode", ["packed", "strided"])
def test_degenerate_monolithic_tiers(boundary, col_mode):
    """1x1 grid: serialized == r12 phase == per-slab partitioned ==
    oracle for both column transports (the channel machinery statically
    elided; the region-split compute is the only live difference)."""
    filt = filters.get_filter("blur3")
    dims = (24, 36) if boundary == "periodic" else (37, 53)
    img = imageio.generate_test_image(*dims, "grey", seed=61)
    want = oracle.run_serial_u8(img, filt, 4, boundary=boundary)
    outs = {}
    for tier, (ov, part) in (("ser", (False, True)),
                             ("phase", (True, False)),
                             ("slab", (True, True))):
        outs[tier] = _run(img, filt, 4, (1, 1), boundary=boundary,
                          fuse=2, overlap=ov, col_mode=col_mode,
                          partitioned=part)
    np.testing.assert_array_equal(outs["slab"], want)
    np.testing.assert_array_equal(outs["slab"], outs["ser"])
    np.testing.assert_array_equal(outs["phase"], outs["ser"])


@pytest.mark.parametrize("col_mode", ["packed", "strided"])
def test_degenerate_tiled_tiers(col_mode):
    """Forced tiled kernel on 1x1 (multi-window grid): all three
    channel tiers byte-identical, both transports."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(96, 384, "grey", seed=62)
    want = oracle.run_serial_u8(img, filt, 4)
    outs = {}
    for tier, (ov, part) in (("ser", (False, True)),
                             ("slab", (True, True))):
        outs[tier] = _run(img, filt, 4, (1, 1), fuse=2, overlap=ov,
                          col_mode=col_mode, partitioned=part,
                          tiled=True, tile=(32, 128))
    np.testing.assert_array_equal(outs["slab"], want)
    np.testing.assert_array_equal(outs["slab"], outs["ser"])


@pytest.mark.parametrize("mesh_shape", [(1, 8), (4, 1), (2, 2)])
@pytest.mark.parametrize("partitioned", [True, False])
def test_tiled_one_long_axis_traces_every_ledger(mesh_shape, partitioned):
    """TRACE-level regression pin (no faithful interpreter needed —
    jax.eval_shape runs the kernel's python body): the tiled kernel's
    retirement helpers must be constructible on grids with a MISSING
    axis, because the legacy phase ledger traces them under dynamic
    predicates.  First cut crashed with AttributeError on (1, N) grids
    (plan.slab('up') is None when R == 1)."""
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES

    filt = filters.get_filter("blur3")
    mesh = _mesh(mesh_shape)
    R, C = mesh_shape
    x = np.zeros((1, R * 32, C * 256), np.float32)

    def body(v):
        return pallas_rdma.fused_rdma_step(
            v, filt, mesh_shape, "zero", quantize=True, tiled=True,
            tile=(8, 128), fuse=1, valid_hw=(R * 32, C * 256),
            overlap=True, partitioned=partitioned)

    jax.eval_shape(jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES),
        out_specs=P(None, *AXES), check_vma=False)), x)


@pytest.mark.parametrize("mesh_shape", [(2, 4), (2, 2), (1, 8), (4, 1)])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_partitioned_monolithic_protocol(mesh_shape, boundary):
    """Full protocol under REAL (simulated) in-flight DMAs: per-slab
    partitioned == r12 phase == serialized == oracle on the ISSUE's
    grid matrix, both boundaries, fuse 1/2/4, both column transports."""
    filt = filters.get_filter("blur3")
    if boundary == "periodic":
        dims = (mesh_shape[0] * 16, mesh_shape[1] * 16)
    else:
        dims = (mesh_shape[0] * 16 + 5, mesh_shape[1] * 16 + 3)
    img = imageio.generate_test_image(*dims, "grey", seed=63)
    for fuse in (1, 2, 4):
        iters = 2 * fuse
        want = oracle.run_serial_u8(img, filt, iters, boundary=boundary)
        for cm in ("packed", "strided"):
            slab = _run(img, filt, iters, mesh_shape, boundary=boundary,
                        fuse=fuse, overlap=True, col_mode=cm,
                        partitioned=True)
            phase = _run(img, filt, iters, mesh_shape, boundary=boundary,
                         fuse=fuse, overlap=True, col_mode=cm,
                         partitioned=False)
            ser = _run(img, filt, iters, mesh_shape, boundary=boundary,
                       fuse=fuse, overlap=False, col_mode=cm)
            np.testing.assert_array_equal(slab, want)
            np.testing.assert_array_equal(slab, phase)
            np.testing.assert_array_equal(slab, ser)


@pytest.mark.parametrize("col_mode", ["packed", "strided"])
def test_partitioned_tiled_protocol(col_mode):
    """Tiled kernel on 2x2: per-slab ledger + rotated rim-last
    traversal + packed/strided transport reproduce serialized bytes."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(64, 256, "grey", seed=64)
    for fuse in (1, 2):
        slab = _run(img, filt, 2 * fuse, (2, 2), fuse=fuse, overlap=True,
                    col_mode=col_mode, partitioned=True, tiled=True,
                    tile=(16, 128))
        ser = _run(img, filt, 2 * fuse, (2, 2), fuse=fuse, overlap=False,
                   col_mode=col_mode, tiled=True, tile=(16, 128))
        want = oracle.run_serial_u8(img, filt, 2 * fuse)
        np.testing.assert_array_equal(slab, ser)
        np.testing.assert_array_equal(slab, want)


def test_partitioned_race_detector():
    """The interpreter's vector-clock race detector over the per-slab
    protocol with the packed transport: every region read must be
    provably ordered against the in-flight slab/stage writes."""
    from jax.sharding import PartitionSpec as P
    from jax.experimental.pallas import tpu as pltpu

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES

    filt = filters.get_filter("blur3")
    mesh = _mesh((2, 2))
    img = imageio.generate_test_image(24, 36, "grey", seed=65)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   detect_races=True)

    def body(v):
        import jax.lax as lax

        def one(_, cur):
            return pallas_rdma.fused_rdma_step(
                cur, filt, (2, 2), "zero", quantize=True, interpret=params,
                fuse=2, valid_hw=(24, 36), overlap=True, col_mode="packed",
                partitioned=True)
        return lax.fori_loop(0, 2, one, v)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    want = oracle.run_serial_u8(img, filt, 4)
    np.testing.assert_array_equal(np.asarray(out)[0].astype(np.uint8), want)


# ---------------------------------------------------------------------------
# Cost model: the split setup/transfer exchange term, pinned constants.
# ---------------------------------------------------------------------------


def test_exchange_setup_transfer_split_pinned():
    """Drift guard for the round-16 constants and the split's algebra:
    persistent zeroes EXACTLY the setup term; the column-transport
    terms recompute from their documented formulas."""
    from parallel_convolution_tpu.tuning import costmodel as cm

    assert cm.EXCHANGE_SETUP_S == 1.5e-6
    assert cm.STRIDED_ROW_DESC_S == 15e-9
    assert cm.PERSISTENT_BACKENDS == ("pallas_rdma",)
    hw = cm.TPU_V5E
    grid, block, radius, fuse, storage = (2, 4), (256, 128), 1, 4, "f32"
    T, (bh, bw) = fuse, block
    non = cm.exchange_seconds_per_px_iter(grid, block, radius, fuse,
                                          storage, hw, persistent=False,
                                          col_mode="packed")
    per = cm.exchange_seconds_per_px_iter(grid, block, radius, fuse,
                                          storage, hw, persistent=True,
                                          col_mode="packed")
    assert non - per == pytest.approx(
        2.0 * cm.EXCHANGE_SETUP_S / (T * bh * bw), rel=1e-12)
    # Column-transport terms from the documented formulas.
    d = radius * T
    rows = bh + 2 * d
    assert cm.col_transport_seconds_per_round(
        block, radius, fuse, storage, hw, "strided") == pytest.approx(
        2.0 * rows * cm.STRIDED_ROW_DESC_S, rel=1e-12)
    assert cm.col_transport_seconds_per_round(
        block, radius, fuse, storage, hw, "packed") == pytest.approx(
        2.0 * 4.0 * rows * d * 4 / (hw.hbm_gbps * 1e9), rel=1e-12)
    # A 1-extent column axis has no transport at all (and zero total on
    # a 1x1 grid — both terms statically elided, like the kernels).
    row_only = cm.exchange_seconds_per_px_iter(
        (4, 1), block, radius, fuse, storage, hw, persistent=True,
        col_mode="strided")
    assert row_only == cm.exchange_seconds_per_px_iter(
        (4, 1), block, radius, fuse, storage, hw, persistent=True,
        col_mode="packed")
    assert cm.exchange_seconds_per_px_iter(
        (1, 1), block, radius, fuse, storage, hw) == 0.0
    with pytest.raises(ValueError, match="col_mode"):
        cm.col_transport_seconds_per_round(block, radius, fuse, storage,
                                           hw, "auto")


def test_pick_col_mode_crossover_and_determinism():
    """The derived-datatypes decision: thin slabs stage (packed), deep
    slabs go direct strided — and the verdict is the argmin of the two
    transport terms by construction, deterministic per identity."""
    from parallel_convolution_tpu.tuning import costmodel as cm

    hw = cm.TPU_V5E
    for block, radius, fuse, storage in (
            ((256, 128), 1, 1, "f32"), ((256, 128), 1, 8, "f32"),
            ((2048, 1024), 2, 4, "bf16"), ((64, 128), 1, 2, "u8")):
        pick = cm.pick_col_mode((2, 4), block, radius, fuse, storage, hw)
        p = cm.col_transport_seconds_per_round(block, radius, fuse,
                                               storage, hw, "packed")
        s = cm.col_transport_seconds_per_round(block, radius, fuse,
                                               storage, hw, "strided")
        assert pick == ("packed" if p <= s else "strided")
        assert pick == cm.pick_col_mode((2, 4), block, radius, fuse,
                                        storage, hw)
    assert cm.pick_col_mode((4, 1), (256, 128), 1, 1, "f32", hw) == "packed"


def test_predict_prices_col_mode_only_on_persistent_tiers():
    from parallel_convolution_tpu.tuning import costmodel as cm

    hw = cm.TPU_V5E
    args = ("f32", 4, None, (1, 4096, 4096), (2048, 1024), (2, 4), 3,
            False, True, hw)
    assert cm.predict_seconds_per_px_iter(
        "pallas", *args, col_mode="packed") == cm.predict_seconds_per_px_iter(
        "pallas", *args, col_mode="strided")
    assert cm.predict_seconds_per_px_iter(
        "pallas_rdma", *args, col_mode="packed") != (
        cm.predict_seconds_per_px_iter(
            "pallas_rdma", *args, col_mode="strided"))


# ---------------------------------------------------------------------------
# Resolution + threading: dispatch, tuner, plans, bench, serving.
# ---------------------------------------------------------------------------


def test_resolve_col_mode_clamps():
    mesh = _mesh((2, 4))
    # Non-persistent forms: the knob is inert, normalized to 'packed'.
    assert step.resolve_col_mode("strided", "shifted", mesh, (8, 8), 1, 1,
                                 "f32") == "packed"
    assert step.resolve_col_mode(None, "pallas", mesh, (8, 8), 1, 1,
                                 "f32") == "packed"
    # Persistent form with a remote column axis: explicit honored, auto
    # goes to the model.
    assert step.resolve_col_mode("strided", "pallas_rdma", mesh, (8, 8),
                                 1, 1, "f32") == "strided"
    auto = step.resolve_col_mode("auto", "pallas_rdma", mesh, (8, 8), 1,
                                 1, "f32")
    assert auto in ("packed", "strided")
    assert step.resolve_col_mode(None, "pallas_rdma", mesh, (8, 8), 1, 1,
                                 "f32") == auto
    # No remote column axis: even an explicit 'strided' normalizes —
    # both transports compile the identical statically-elided program,
    # so one program gets ONE resolved identity (keys never split).
    for shape in ((1, 1), (4, 1)):
        assert step.resolve_col_mode("strided", "pallas_rdma",
                                     _mesh(shape), (8, 8), 1, 1,
                                     "f32") == "packed"
    with pytest.raises(ValueError, match="col_mode"):
        step.resolve_col_mode("dense", "pallas_rdma", mesh, (8, 8), 1, 1,
                              "f32")
    assert step.clamp_col_mode("strided", "pallas") == "packed"
    assert step.clamp_col_mode("strided", "pallas_rdma") == "strided"


def test_candidate_space_col_modes():
    from parallel_convolution_tpu.tuning import search
    from parallel_convolution_tpu.tuning.plans import Workload

    filt = filters.get_filter("blur3")
    w = Workload.from_mesh(_mesh((2, 4)), filt, (1, 512, 512))
    cands = search.enumerate_candidates(w)
    rdma = {c.col_mode for c in cands if c.backend == "pallas_rdma"}
    assert rdma == {"packed", "strided"}
    assert {c.col_mode for c in cands
            if c.backend != "pallas_rdma"} == {"packed"}
    # A pinned mode prunes the persistent tier's pair to one.
    pinned = search.enumerate_candidates(w, col_mode="strided")
    assert {c.col_mode for c in pinned
            if c.backend == "pallas_rdma"} == {"strided"}
    # No remote column axis: both modes compile the identical program —
    # only the canonical twin is enumerated (no wasted measurements).
    w41 = Workload.from_mesh(_mesh((4, 1)), filt, (1, 512, 512))
    assert {c.col_mode for c in search.enumerate_candidates(w41)} == {
        "packed"}


def test_plan_record_col_mode_roundtrip(tmp_path):
    """Plans persist col_mode; legacy records (no key) load as 'packed'
    — byte-identical to every mode, so no schema bump."""
    from parallel_convolution_tpu.tuning.plans import (
        PLAN_SCHEMA, Plan, PlanCache, Workload,
    )

    filt = filters.get_filter("blur3")
    w = Workload.from_mesh(_mesh((2, 4)), filt, (1, 512, 512))
    cache = PlanCache()
    cache.put(w, Plan("pallas_rdma", fuse=4, col_mode="strided",
                      source="measured"))
    p = str(tmp_path / "plans.json")
    cache.save(p)
    loaded = PlanCache.load(p)
    plan = loaded.exact(w)
    assert plan is not None and plan.col_mode == "strided"
    rec = loaded.records[w.key()]
    rec.pop("col_mode")   # a pre-r16 tuner's record
    assert Plan.from_record(rec).col_mode == "packed"
    assert PLAN_SCHEMA == 1  # explicitly NO schema bump


def test_resolve_from_plan_col_mode():
    from parallel_convolution_tpu import tuning
    from parallel_convolution_tpu.tuning.plans import Plan, PlanCache, Workload

    filt = filters.get_filter("blur3")
    mesh = _mesh((2, 4))
    w = Workload.from_mesh(mesh, filt, (1, 512, 512))
    cache = PlanCache()
    cache.put(w, Plan("pallas_rdma", fuse=4, col_mode="strided",
                      source="measured"))
    res = tuning.resolve(mesh, filt, (1, 512, 512), plans=cache)
    assert (res.backend, res.col_mode) == ("pallas_rdma", "strided")
    # Explicit request overrides the stored verdict.
    res = tuning.resolve(mesh, filt, (1, 512, 512), plans=cache,
                         col_mode="packed")
    assert res.col_mode == "packed"
    # A stored strided verdict on a NON-persistent plan normalizes.
    cache2 = PlanCache()
    cache2.put(w, Plan("shifted", col_mode="strided", source="measured"))
    res = tuning.resolve(mesh, filt, (1, 512, 512), plans=cache2)
    assert res.col_mode == "packed"


def test_bench_row_stamps_col_mode():
    from parallel_convolution_tpu.utils import bench

    filt = filters.get_filter("blur3")
    # 1x1 grid: no column transport exists, so even an explicit
    # 'strided' request stamps the canonical normalized label — the row
    # states the PROGRAM, and there is only one program here.
    row = bench.bench_iterate((16, 128), filt, 2, mesh=_mesh((1, 1)),
                              backend="pallas_rdma", reps=1,
                              col_mode="strided")
    assert row["col_mode"] == "packed"
    assert row["effective_backend"] == "pallas_rdma"
    row = bench.bench_iterate((16, 64), filt, 2, mesh=_mesh((1, 1)),
                              backend="shifted", reps=1,
                              col_mode="strided")
    assert row["col_mode"] == "packed"  # inert off the persistent tier


def test_probe_key_distinguishes_col_mode():
    from parallel_convolution_tpu.resilience import degrade

    filt = filters.get_filter("blur3")
    mesh = _mesh((1, 1))
    k1 = degrade._probe_key(mesh, filt, "pallas_rdma", True, 1, "zero",
                            None, "f32", (8, 8), overlap=False,
                            col_mode="packed")
    k2 = degrade._probe_key(mesh, filt, "pallas_rdma", True, 1, "zero",
                            None, "f32", (8, 8), overlap=False,
                            col_mode="strided")
    assert k1 != k2


def test_engine_key_carries_resolved_col_mode():
    from parallel_convolution_tpu.serving.engine import WarmEngine

    # A grid WITH a remote column axis: the two transports are distinct
    # compiled programs, so they key separately (resolve_key never
    # compiles — safe on stock jax).
    eng24 = WarmEngine(mesh=_mesh((2, 4)))
    k_p, _ = eng24.resolve_key((1, 64, 512), backend="pallas_rdma",
                               iters=2, col_mode="packed")
    k_s, _ = eng24.resolve_key((1, 64, 512), backend="pallas_rdma",
                               iters=2, col_mode="strided")
    assert k_p.col_mode == "packed" and k_s.col_mode == "strided"
    assert k_p != k_s
    eng = WarmEngine(mesh=_mesh((1, 1)))
    # None (absent) and 'auto' resolve to the SAME concrete key — one
    # warm executable for auto + explicit requests, the backend/overlap
    # rule applied to the column transport.
    k_none, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma",
                                iters=2)
    k_auto, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma",
                                iters=2, col_mode="auto")
    assert k_none == k_auto
    assert k_none.col_mode in ("packed", "strided")
    # No remote column axis: an explicit 'strided' request compiles the
    # IDENTICAL statically-elided program, so it shares the key too —
    # never two warm entries for one executable.
    k_str1, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma",
                                iters=2, col_mode="strided")
    assert k_str1 == k_none
    # Non-persistent backends key the canonical inert label.
    k_sh, _ = eng.resolve_key((1, 16, 128), backend="shifted", iters=2,
                              col_mode="strided")
    assert k_sh.col_mode == "packed"
    with pytest.raises(ValueError, match="col_mode"):
        from parallel_convolution_tpu.serving.engine import EngineKey

        EngineKey(shape=(1, 16, 128), col_mode="auto").validate()


def test_service_response_stamps_col_mode():
    from parallel_convolution_tpu.serving.service import (
        ConvolutionService, Request,
    )

    img = imageio.generate_test_image(16, 128, "grey", seed=66)
    svc = ConvolutionService(mesh=_mesh((1, 1)), max_delay_s=0.001)
    try:
        # 1x1 grid: the strided request normalizes (no column transport
        # exists) and the response stamps the RESOLVED value.
        res = svc.submit(Request(image=img, iters=2,
                                 backend="pallas_rdma",
                                 col_mode="strided"))
        assert res.ok and res.col_mode == "packed"
        want = oracle.run_serial_u8(img, filters.get_filter("blur3"), 2)
        np.testing.assert_array_equal(res.image, want)
        res2 = svc.submit(Request(image=img, iters=2, backend="shifted"))
        assert res2.ok and res2.col_mode == "packed"
    finally:
        svc.close()


def test_wire_codec_roundtrips_col_mode():
    from parallel_convolution_tpu.serving import frontend

    req = frontend.decode_request({
        "rows": 4, "cols": 4, "mode": "grey",
        "image_b64": __import__("base64").b64encode(
            bytes(16)).decode("ascii"),
        "col_mode": "strided"})
    assert req.col_mode == "strided"
    req = frontend.decode_request({
        "rows": 4, "cols": 4, "mode": "grey",
        "image_b64": __import__("base64").b64encode(
            bytes(16)).decode("ascii")})
    assert req.col_mode is None


# ---------------------------------------------------------------------------
# Channel reuse through real runs + the slab-wait attribution series.
# ---------------------------------------------------------------------------


def test_channel_reuse_flat_across_converge_chunks():
    """A fused multi-chunk converge run builds exactly one plan per
    distinct exchange identity (the fused chunk + the pair step) and
    every later chunk reuses them — the acceptance criterion's
    'descriptor-plan builds == distinct identities, flat'."""
    filt = filters.get_filter("blur3")
    mesh = _mesh((1, 1))
    img = imageio.generate_test_image(24, 32, "grey", seed=67)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    channels.reset()
    out, iters = step.sharded_converge(
        x, filt, tol=0.0, max_iters=6, check_every=3, mesh=mesh,
        quantize=True, backend="pallas_rdma", fuse=2)
    first = channels.stats()
    assert first["builds"] == 2  # fused chunk + single-step identities
    assert iters == 6
    out2, _ = step.sharded_converge(
        x, filt, tol=0.0, max_iters=12, check_every=3, mesh=mesh,
        quantize=True, backend="pallas_rdma", fuse=2)
    assert channels.stats()["builds"] == first["builds"]
    want = oracle.run_serial_u8(img, filt, 6)
    got = np.clip(np.rint(np.asarray(out)), 0, 255).astype(np.uint8)[0]
    np.testing.assert_array_equal(got, want)


def test_mg_level_schedule_caches_channel_identities():
    from parallel_convolution_tpu.solvers import multigrid as mg

    filt = filters.get_filter("blur3")
    levels = mg.plan_levels(_mesh((1, 1)), (96, 64), filt.radius, "zero")
    assert len(levels) > 1
    channels.reset()
    keys = mg.warm_level_channels(levels, filt.radius, "zero", "packed")
    assert len(keys) == len(levels)
    assert channels.stats()["builds"] == len(set(keys))
    mg.warm_level_channels(levels, filt.radius, "zero", "packed")
    s = channels.stats()
    assert s["builds"] == len(set(keys))  # flat: bound once per level
    assert s["hits"] >= len(keys)
    # Each level's identity states ITS OWN geometry.
    assert [k.block_hw for k in keys] == [lv.block_hw for lv in levels]


def test_slab_wait_series_and_event_col_mode():
    """record_step with a wall emits the per-slab wait counter split by
    direction x exposed/hidden, shares summing to the exchange wall."""
    from parallel_convolution_tpu.obs import attribution, metrics

    was = metrics.enabled()
    metrics.reset()
    metrics.set_enabled(True)
    try:
        att = attribution.record_step(
            backend="pallas_rdma", grid=(2, 4), block_hw=(256, 128),
            radius=1, fuse=4, iters=8, channels=1, storage="f32",
            boundary="zero", wall_s=0.5, shape=(1, 512, 512),
            platform="tpu", device_kind="tpu-v5e", overlap=True,
            col_mode="strided")
        assert att is not None
        snap = metrics.snapshot()
        m = next(x for x in snap["metrics"]
                 if x["name"] == "pctpu_halo_slab_wait_seconds")
        got = {(s["labels"]["direction"], s["labels"]["which"]): s["value"]
               for s in m["series"]}
        assert {d for d, _ in got} == {"north", "south", "east", "west"}
        assert {w for _, w in got} == {"exposed", "hidden"}
        exposed = sum(v for (d, w), v in got.items() if w == "exposed")
        assert exposed == pytest.approx(
            0.5 * att["exchange_fraction"], rel=1e-6)
    finally:
        metrics.reset()
        metrics.set_enabled(was)
