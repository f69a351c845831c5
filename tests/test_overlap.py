"""Overlapped halo pipeline: byte-identity, resolution, and cost model.

The interior-first restructure of the RDMA kernels (``overlap=True``)
must be byte-identical to the serialized order everywhere — the only
thing it may change is WHEN independent pixels compute relative to the
in-flight ghost DMAs.  Three proof tiers:

* degenerate grids (any jax): extent-1 axes statically elide every RDMA
  construct, so the monolithic kernel's interior/band REGION-SPLIT
  compute — the overlap path's only new math when no DMA exists — is
  pinned against both the serialized twin and the oracle;
* the full multi-device protocol (2x4 / 2x2 / 1-long-axis meshes, both
  kernels) under the DMA-faithful TPU interpreter — skips with cause on
  a jax without it, exactly like tests/test_rdma.py;
* the resolution layer: the knob is a clamped request (RDMA tier only,
  force-serialized under interpreted Pallas unless the byte-proof env
  hatch is set), and every row stamps the RESOLVED value.

Plus drift guards pinning the cost model's overlap term
(max(compute, exchange) replacing compute + exchange when legal) so the
constants ``backend="auto"`` ranks with cannot silently drift from the
kernels' legality rules.
"""

import warnings

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.utils import imageio



def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]],
                                   shape)


def _run(img, filt, iters, mesh_shape, *, boundary="zero", fuse=1,
         overlap=False, storage=np.float32, tiled=None, tile=None):
    """Chained fused_rdma_step invocations straight at the kernel (the
    dispatch layer's interpret clamp deliberately bypassed: this file
    proves the overlapped PROGRAM's bytes)."""
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES, padded_extent

    mesh = _mesh(mesh_shape)
    x = imageio.interleaved_to_planar(img).astype(storage)
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
                overlap=overlap)
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
# Region partition unit (the geometry both the kernel and the cost model's
# legality predicate rely on).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,w,d", [(32, 48, 2), (8, 8, 4), (5, 40, 2),
                                   (3, 3, 2), (16, 4, 1), (1, 1, 1),
                                   (64, 64, 8)])
def test_overlap_regions_partition_exact(h, w, d):
    """The interior/row-band/col-band rectangles tile the (h, w) block
    exactly — every output pixel exactly once, any geometry."""
    from parallel_convolution_tpu.ops.pallas_rdma import overlap_regions

    interior, row_bands, col_bands = overlap_regions(h, w, d)
    cover = np.zeros((h, w), np.int32)
    for (r0, r1, c0, c1) in interior + row_bands + col_bands:
        assert 0 <= r0 < r1 <= h and 0 <= c0 < c1 <= w
        cover[r0:r1, c0:c1] += 1
    np.testing.assert_array_equal(cover, np.ones((h, w), np.int32))
    # Interior is exactly the ghost-free box, empty when the block is
    # all rim (the cost model's overlap_legal condition).
    if min(h, w) > 2 * d:
        assert interior == [(d, h - d, d, w - d)]
    else:
        assert interior == []


def test_overlap_legal_mirrors_regions():
    """costmodel.overlap_legal == "interior non-empty on an RDMA tier
    with a collective" — drift-guarded against the kernel's partition."""
    from parallel_convolution_tpu.ops.pallas_rdma import overlap_regions
    from parallel_convolution_tpu.tuning import costmodel

    for block in ((32, 32), (8, 8), (4, 64), (2, 2)):
        for r, T in ((1, 1), (1, 4), (2, 2)):
            want = bool(overlap_regions(block[0], block[1], r * T)[0])
            assert costmodel.overlap_legal(
                "pallas_rdma", (2, 2), block, r, T) == want
    # Never for non-RDMA tiers or a 1x1 grid.
    assert not costmodel.overlap_legal("pallas", (2, 2), (64, 64), 1, 1)
    assert not costmodel.overlap_legal("shifted", (2, 2), (64, 64), 1, 1)
    assert not costmodel.overlap_legal("pallas_rdma", (1, 1), (64, 64), 1, 1)


# ---------------------------------------------------------------------------
# Degenerate grids: the region-split compute on any jax.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fuse", [1, 2, 4])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_overlap_degenerate_monolithic(fuse, boundary):
    """1x1 grid, overlap=True: the 5-region interior-first compute must
    equal the serialized whole-block program AND the oracle, both
    boundaries, fuse 1/2/4 (odd dims exercise the pad-rim masking)."""
    filt = filters.get_filter("blur3")
    dims = (24, 36) if boundary == "periodic" else (37, 53)
    img = imageio.generate_test_image(*dims, "grey", seed=41)
    iters = 4 * fuse
    want = oracle.run_serial_u8(img, filt, iters, boundary=boundary)
    ov = _run(img, filt, iters, (1, 1), boundary=boundary, fuse=fuse,
              overlap=True)
    ser = _run(img, filt, iters, (1, 1), boundary=boundary, fuse=fuse,
               overlap=False)
    np.testing.assert_array_equal(ov, ser)
    np.testing.assert_array_equal(ov, want)


def test_overlap_degenerate_monolithic_radius2_u8():
    """radius-2 taps + u8 carry through the region split (deep rim)."""
    filt = filters.get_filter("gaussian5")
    img = imageio.generate_test_image(41, 57, "grey", seed=42)
    ov = _run(img, filt, 4, (1, 1), fuse=2, overlap=True,
              storage=np.uint8)
    want = oracle.run_serial_u8(img, filt, 4)
    np.testing.assert_array_equal(ov, want)


def test_overlap_degenerate_block_all_rim():
    """A block smaller than 2*d on one axis: interior empties out and
    the bands absorb everything — still byte-exact."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(7, 64, "grey", seed=43)
    ov = _run(img, filt, 3, (1, 1), fuse=3, overlap=True)
    want = oracle.run_serial_u8(img, filt, 3)
    np.testing.assert_array_equal(ov, want)


@pytest.mark.parametrize("fuse", [2, 4])
def test_overlap_degenerate_tiled(fuse):
    """Tiled kernel with overlap=True on 1x1: no remote axis exists, so
    the program is the serialized one verbatim — pinned byte-exact."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(96, 384, "grey", seed=44)
    ov = _run(img, filt, 2 * fuse, (1, 1), fuse=fuse, overlap=True,
              tiled=True, tile=(32, 128))
    ser = _run(img, filt, 2 * fuse, (1, 1), fuse=fuse, overlap=False,
               tiled=True, tile=(32, 128))
    want = oracle.run_serial_u8(img, filt, 2 * fuse)
    np.testing.assert_array_equal(ov, ser)
    np.testing.assert_array_equal(ov, want)


# ---------------------------------------------------------------------------
# Full protocol (faithful interpreter / silicon only): overlap ==
# serialized == oracle on real multi-device grids, both kernels.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mesh_shape", [(2, 4), (2, 2), (1, 8), (4, 1)])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_overlap_monolithic_protocol(mesh_shape, boundary):
    """Interior-first under REAL (simulated) in-flight DMAs: 2x4 / 2x2 /
    1-long-axis grids, both boundaries — overlap == serialized ==
    oracle.  The 1-long-axis grids pin the statically-elided-axis forms
    (row-only / col-only exchange under the pipeline)."""
    filt = filters.get_filter("blur3")
    if boundary == "periodic":
        dims = (mesh_shape[0] * 16, mesh_shape[1] * 16)
    else:
        dims = (mesh_shape[0] * 16 + 5, mesh_shape[1] * 16 + 3)
    img = imageio.generate_test_image(*dims, "grey", seed=45)
    for fuse in (1, 2, 4):
        iters = 2 * fuse
        want = oracle.run_serial_u8(img, filt, iters, boundary=boundary)
        ov = _run(img, filt, iters, mesh_shape, boundary=boundary,
                  fuse=fuse, overlap=True)
        ser = _run(img, filt, iters, mesh_shape, boundary=boundary,
                   fuse=fuse, overlap=False)
        np.testing.assert_array_equal(ov, ser)
        np.testing.assert_array_equal(ov, want)


@pytest.mark.parametrize("fuse", [1, 2, 4])
def test_overlap_tiled_protocol(fuse):
    """Tiled kernel on 2x2: rotated rim-last traversal + deferred
    semaphore waits must reproduce the serialized bytes exactly."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(64, 256, "grey", seed=46)
    ov = _run(img, filt, 2 * fuse, (2, 2), fuse=fuse, overlap=True,
              tiled=True, tile=(16, 128))
    ser = _run(img, filt, 2 * fuse, (2, 2), fuse=fuse, overlap=False,
               tiled=True, tile=(16, 128))
    want = oracle.run_serial_u8(img, filt, 2 * fuse)
    np.testing.assert_array_equal(ov, ser)
    np.testing.assert_array_equal(ov, want)


def test_overlap_monolithic_race_detector(grey_small):
    """The interpreter's vector-clock race detector over the overlapped
    protocol: interior/band reads vs in-flight ghost writes must be
    provably ordered (disjoint or semaphore-separated) on every pair."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES

    filt = filters.get_filter("blur3")
    mesh = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)[
        :, :24, :36]
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   detect_races=True)

    def body(v):
        import jax.lax as lax

        def one(_, cur):
            return pallas_rdma.fused_rdma_step(
                cur, filt, (2, 2), "zero", quantize=True, interpret=params,
                fuse=2, valid_hw=(24, 36), overlap=True)
        return lax.fori_loop(0, 2, one, v)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    want = oracle.run_serial_u8(x[0].astype(np.uint8), filt, 4)
    np.testing.assert_array_equal(np.asarray(out)[0].astype(np.uint8), want)


# ---------------------------------------------------------------------------
# Resolution: the knob is a clamped request; rows stamp the RESOLVED value.
# ---------------------------------------------------------------------------


def test_resolve_overlap_clamps(monkeypatch):
    monkeypatch.delenv(step.OVERLAP_INTERPRET_ENV, raising=False)
    mesh = _mesh((2, 4))
    assert step.resolve_overlap(None, "pallas_rdma", mesh) is False
    assert step.resolve_overlap(False, "pallas_rdma", mesh) is False
    # Non-RDMA backend: force-serialized with a one-time warning.
    step._OVERLAP_WARNED.clear()
    with pytest.warns(UserWarning, match="no overlapped halo pipeline"):
        assert step.resolve_overlap(True, "shifted", mesh) is False
    # Interpreted mesh: force-serialized with a one-time warning...
    with pytest.warns(UserWarning, match="force-serialized"):
        assert step.resolve_overlap(True, "pallas_rdma", mesh) is False
    # ...warn-once: the second request is silent (same cause).
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert step.resolve_overlap(True, "pallas_rdma", mesh) is False
    # The byte-proof env hatch engages the overlapped program anyway.
    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    assert step.resolve_overlap(True, "pallas_rdma", mesh) is True


def test_bench_row_stamps_resolved_overlap(monkeypatch):
    """bench_iterate rows stamp the knob the executable was ACTUALLY
    compiled with — True only when the request survives every clamp."""
    from parallel_convolution_tpu.utils import bench

    filt = filters.get_filter("blur3")
    step._OVERLAP_WARNED.clear()
    monkeypatch.delenv(step.OVERLAP_INTERPRET_ENV, raising=False)
    with pytest.warns(UserWarning):
        row = bench.bench_iterate((16, 128), filt, 2, mesh=_mesh((1, 1)),
                                  backend="pallas_rdma", reps=1,
                                  overlap=True)
    assert row["overlap"] is False  # interpret clamp
    assert row["exchange_hidden_fraction"] == 0.0
    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    row = bench.bench_iterate((16, 128), filt, 2, mesh=_mesh((1, 1)),
                              backend="pallas_rdma", reps=1, overlap=True)
    assert row["overlap"] is True
    assert row["effective_backend"] == "pallas_rdma"
    # Serialized rows are unchanged in shape: the knob is always present.
    row = bench.bench_iterate((16, 64), filt, 2, mesh=_mesh((1, 1)),
                              backend="shifted", reps=1)
    assert row["overlap"] is False


def test_driver_overlap_bytes_via_dispatch(monkeypatch):
    """The full dispatch stack (sharded_iterate -> resolve_overlap ->
    _build_iterate) drives the overlapped program under the env hatch,
    byte-exact vs the serialized run and the oracle."""
    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(37, 53, "grey", seed=47)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    mesh = _mesh((1, 1))
    outs = {}
    for ov in (False, True):
        out = step.sharded_iterate(x, filt, 6, mesh=mesh, quantize=True,
                                   backend="pallas_rdma", fuse=2,
                                   overlap=ov)
        outs[ov] = imageio.planar_to_interleaved(
            np.asarray(out).astype(np.uint8))
    want = oracle.run_serial_u8(img, filt, 6)
    np.testing.assert_array_equal(outs[True], outs[False])
    np.testing.assert_array_equal(outs[True], want)


def test_probe_key_distinguishes_overlap():
    """The degrade probe cache keys on the overlap form: the overlapped
    RDMA program is a different kernel than the serialized one."""
    from parallel_convolution_tpu.resilience import degrade

    filt = filters.get_filter("blur3")
    mesh = _mesh((1, 1))
    k1 = degrade._probe_key(mesh, filt, "pallas_rdma", True, 1, "zero",
                            None, "f32", (8, 8), overlap=False)
    k2 = degrade._probe_key(mesh, filt, "pallas_rdma", True, 1, "zero",
                            None, "f32", (8, 8), overlap=True)
    assert k1 != k2


# ---------------------------------------------------------------------------
# Cost model drift guards: the overlap term's constants.
# ---------------------------------------------------------------------------


def test_predict_overlap_is_max_not_sum():
    """The overlap factor: max(compute, exchange) replaces
    compute + exchange exactly — pinned by recomputing both sides from
    the model's own components."""
    from parallel_convolution_tpu.tuning import costmodel as cm

    hw = cm.TPU_V5E
    backend, storage, fuse, tile = "pallas_rdma", "f32", 4, None
    shape, block, grid, k = (1, 4096, 4096), (2048, 1024), (2, 4), 3
    radius = 1
    t_hbm = cm.hbm_bytes_per_px_iter(backend, storage, fuse, tile, block,
                                     radius, shape) / (hw.hbm_gbps * 1e9)
    t_flop = cm.flops_per_px_iter(k, False, True, fuse, block,
                                  radius) / (hw.flop_gops * 1e9)
    # The RDMA tier binds persistent channels (round 16): its exchange
    # term zeroes the per-phase setup and prices the packed column
    # transport — recompute the SAME term predict uses.
    t_ex = cm.exchange_seconds_per_px_iter(grid, block, radius, fuse,
                                           storage, hw, persistent=True,
                                           col_mode="packed")
    assert t_ex > 0
    serial = cm.predict_seconds_per_px_iter(
        backend, storage, fuse, tile, shape, block, grid, k, False, True,
        hw)
    overlapped = cm.predict_seconds_per_px_iter(
        backend, storage, fuse, tile, shape, block, grid, k, False, True,
        hw, overlap=True)
    assert serial == pytest.approx(max(t_hbm, t_flop) + t_ex, rel=1e-12)
    assert overlapped == pytest.approx(max(max(t_hbm, t_flop), t_ex),
                                       rel=1e-12)
    assert overlapped <= serial
    # Illegal overlap (1x1 grid / wrong tier) silently prices serialized.
    assert cm.predict_seconds_per_px_iter(
        backend, storage, fuse, tile, shape, block, (1, 1), k, False,
        True, hw, overlap=True) == cm.predict_seconds_per_px_iter(
        backend, storage, fuse, tile, shape, block, (1, 1), k, False,
        True, hw)
    assert cm.predict_seconds_per_px_iter(
        "pallas", storage, fuse, tile, shape, block, grid, k, False,
        True, hw, overlap=True) == cm.predict_seconds_per_px_iter(
        "pallas", storage, fuse, tile, shape, block, grid, k, False,
        True, hw)


def test_candidate_space_overlap_variants(monkeypatch):
    """Enumeration: overlap variants exist only for the RDMA tier where
    legal; a pinned False yields none; ranking never prefers the
    overlapped form on a model tie."""
    from parallel_convolution_tpu.tuning import search
    from parallel_convolution_tpu.tuning.plans import Workload

    filt = filters.get_filter("blur3")
    w = Workload.from_mesh(_mesh((2, 4)), filt, (1, 512, 512))
    # Interpreted-Pallas platform (this CPU mesh) without the byte-proof
    # hatch: NO overlap candidates — dispatch would force-serialize them,
    # so the tuner must not measure (or persist) a form that never runs.
    monkeypatch.delenv(step.OVERLAP_INTERPRET_ENV, raising=False)
    assert not [c for c in search.enumerate_candidates(w) if c.overlap]
    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    cands = search.enumerate_candidates(w)
    rdma_ov = [c for c in cands if c.overlap]
    assert rdma_ov and all(c.backend == "pallas_rdma" for c in rdma_ov)
    assert not [c for c in search.enumerate_candidates(w, overlap=False)
                if c.overlap]
    # overlap=True request: RDMA candidates all overlapped, other tiers
    # clamp to serialized rather than emptying the space.
    pinned = search.enumerate_candidates(w, overlap=True)
    assert all(c.overlap == (c.backend == "pallas_rdma") for c in pinned)
    # Tie-break: zero-exchange workload (1x1) enumerates no overlap at
    # all, so serialized always wins flat ties by construction.
    w1 = Workload.from_mesh(_mesh((1, 1)), filt, (1, 64, 64))
    assert not [c for c in search.enumerate_candidates(w1) if c.overlap]


def test_plan_record_overlap_roundtrip(tmp_path):
    """Plans persist the overlap verdict; legacy records (no key) load
    as serialized — the exact pre-overlap behavior, no schema bump."""
    from parallel_convolution_tpu.tuning.plans import Plan, PlanCache, Workload

    filt = filters.get_filter("blur3")
    w = Workload.from_mesh(_mesh((2, 4)), filt, (1, 512, 512))
    cache = PlanCache()
    cache.put(w, Plan("pallas_rdma", fuse=4, overlap=True,
                      source="measured"))
    p = str(tmp_path / "plans.json")
    cache.save(p)
    loaded = PlanCache.load(p)
    plan = loaded.exact(w)
    assert plan is not None and plan.overlap is True
    # Legacy record: strip the key as an old tuner would have written it.
    rec = loaded.records[w.key()]
    rec.pop("overlap")
    assert Plan.from_record(rec).overlap is False


def test_resolve_overlap_from_plan():
    """backend='auto' with an armed plan resolves the stored overlap
    verdict (clamped to the workload's legality) and stamps provenance."""
    from parallel_convolution_tpu import tuning
    from parallel_convolution_tpu.tuning.plans import Plan, PlanCache, Workload

    filt = filters.get_filter("blur3")
    mesh = _mesh((2, 4))
    w = Workload.from_mesh(mesh, filt, (1, 512, 512))
    cache = PlanCache()
    cache.put(w, Plan("pallas_rdma", fuse=4, overlap=True,
                      source="measured"))
    res = tuning.resolve(mesh, filt, (1, 512, 512), plans=cache)
    assert (res.backend, res.fuse, res.overlap) == ("pallas_rdma", 4, True)
    assert res.source == "measured"
    # An explicit overlap=False request overrides the plan's verdict.
    res = tuning.resolve(mesh, filt, (1, 512, 512), plans=cache,
                         overlap=False)
    assert res.overlap is False
    # A pinned fuse that kills the interior re-clamps the stored True:
    # blocks 256x128, fuse=32 -> d=32, 2*d < 128 still legal; use a
    # small image instead so the whole block is rim.
    w2 = Workload.from_mesh(mesh, filt, (1, 8, 8))
    cache2 = PlanCache()
    cache2.put(w2, Plan("pallas_rdma", fuse=1, overlap=True,
                        source="measured"))
    res2 = tuning.resolve(mesh, filt, (1, 8, 8), plans=cache2)
    assert res2.overlap is False  # block 4x2: all rim, overlap illegal


# ---------------------------------------------------------------------------
# Attribution: hidden vs exposed exchange.
# ---------------------------------------------------------------------------


def test_exchange_split_serialized_matches_legacy_series():
    from parallel_convolution_tpu.obs import attribution

    kw = dict(backend="pallas_rdma", storage="f32", shape=(1, 512, 512),
              tile=None, quantize=True, separable=False, platform="tpu",
              device_kind="tpu-v5e")
    frac = attribution.predicted_exchange_fraction(
        (2, 4), (256, 128), 1, 4, **kw)
    split = attribution.predicted_exchange_split(
        (2, 4), (256, 128), 1, 4, **kw)
    assert split["exchange_fraction"] == frac
    assert split["exchange_hidden_fraction"] == 0.0
    assert split["overlap"] is False


def test_exchange_split_overlap_budget():
    """Overlap-adjusted split invariants: hidden + exposed == the whole
    exchange, exposed shrinks vs serialized, 1x1 grids are exactly 0."""
    from parallel_convolution_tpu.obs import attribution
    from parallel_convolution_tpu.tuning import costmodel as cm

    kw = dict(backend="pallas_rdma", storage="f32", shape=(1, 512, 512),
              tile=None, quantize=True, separable=False, platform="tpu",
              device_kind="tpu-v5e")
    grid, block, radius, fuse = (2, 4), (256, 128), 1, 4
    ser = attribution.predicted_exchange_split(grid, block, radius, fuse,
                                               **kw)
    ov = attribution.predicted_exchange_split(grid, block, radius, fuse,
                                              overlap=True, **kw)
    assert ov["overlap"] is True
    assert ov["exchange_fraction"] <= ser["exchange_fraction"]
    assert 0.0 <= ov["exchange_hidden_fraction"] <= 1.0
    # hidden/total + exposed/total == ex/total at the model's quantities.
    hw = cm.hardware_for("tpu", "tpu-v5e")
    ex = cm.exchange_seconds_per_px_iter(grid, block, radius, fuse,
                                         "f32", hw)
    total = ex / max(1e-30, (ov["exchange_fraction"]
                             + ov["exchange_hidden_of_total"]))
    assert total > 0  # consistency: the two shares reassemble the term
    z = attribution.predicted_exchange_split((1, 1), block, radius, fuse,
                                             overlap=True, **kw)
    assert z["exchange_fraction"] == z["exchange_hidden_fraction"] == 0.0


# ---------------------------------------------------------------------------
# Serving: the knob rides the key; responses stamp the resolved value.
# ---------------------------------------------------------------------------


def test_engine_key_carries_resolved_overlap(monkeypatch):
    from parallel_convolution_tpu.serving.engine import WarmEngine

    step._OVERLAP_WARNED.clear()
    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    eng = WarmEngine(mesh=_mesh((1, 1)))
    k_on, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma", iters=2,
                              overlap=True)
    k_off, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma", iters=2,
                               overlap=False)
    assert k_on.overlap is True and k_off.overlap is False
    assert k_on != k_off
    # None (absent) resolves False for explicit backends — the exact
    # pre-overlap key, so old clients share executables with new ones.
    k_def, _ = eng.resolve_key((1, 16, 128), backend="pallas_rdma", iters=2)
    assert k_def == k_off


def test_service_response_stamps_overlap(monkeypatch):
    from parallel_convolution_tpu.serving.service import (
        ConvolutionService, Request,
    )

    monkeypatch.setenv(step.OVERLAP_INTERPRET_ENV, "1")
    img = imageio.generate_test_image(16, 128, "grey", seed=48)
    svc = ConvolutionService(mesh=_mesh((1, 1)), max_delay_s=0.001)
    try:
        res = svc.submit(Request(image=img, iters=2,
                                 backend="pallas_rdma", overlap=True))
        assert res.ok
        assert res.overlap is True
        assert res.exchange_fraction == 0.0  # 1x1 grid: no exchange
        assert res.exchange_hidden_fraction == 0.0
        want = oracle.run_serial_u8(img, filters.get_filter("blur3"), 2)
        np.testing.assert_array_equal(res.image, want)
        res2 = svc.submit(Request(image=img, iters=2, backend="shifted"))
        assert res2.ok and res2.overlap is False
    finally:
        svc.close()
