"""Fused remote-DMA halo kernel: bit-exactness + race-freedom on CPU mesh.

TPU interpret mode simulates remote DMAs, semaphores and per-device
buffers on the virtual CPU mesh, so the cross-device protocol (two-phase
sends, conditional boundary waits, corner propagation) is executed for
real — this is the reference's Isend/Irecv tier moved inside the kernel.
Perf on real multi-chip hardware is explicitly NOT validated here (no
such hardware in this environment); semantics are.
"""

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.utils import imageio

# The cross-device protocol needs the DMA-faithful TPU interpreter
# (simulated remote copies / semaphores / barrier).  On a jax without it
# (0.4.x: no lowering for those primitives on CPU) the multi-device tests
# skip with cause; the degenerate-grid tests below still run — extent-1
# axes statically elide every RDMA construct (pallas_rdma._when), so the
# full fuse compute path is pinned on any jax.


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]], shape)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2), (2, 4), (4, 1),
                                        (1, 8)])
def test_rdma_bitexact_vs_oracle(grey_odd, mesh_shape):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 4)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 4, mesh=_mesh(mesh_shape),
                               quantize=True, backend="pallas_rdma")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_rgb_radius2(rgb_odd):
    # radius-2: 2-wide ghost slabs + 2-hop corners through the RDMA path
    filt = filters.get_filter("gaussian5")
    want = oracle.run_serial_u8(rgb_odd, filt, 3)
    x = imageio.interleaved_to_planar(rgb_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 3, mesh=_mesh((2, 2)),
                               quantize=True, backend="pallas_rdma")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_periodic(grey_small):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_small, filt, 4, boundary="periodic")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    out = step.sharded_iterate(x, filt, 4, mesh=_mesh((2, 2)), quantize=True,
                               backend="pallas_rdma", boundary="periodic")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_u8_storage(grey_odd):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 5)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 5, mesh=_mesh((2, 2)), quantize=True,
                               backend="pallas_rdma", storage="u8")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_race_detector(grey_small):
    """The interpreter's vector-clock race detector over the full protocol.

    This is the framework's race-detection tier (SURVEY.md §5 sanitizers):
    local ghost zeroing vs inbound remote writes are disjoint by design,
    and detect_races=True proves it on every (device, phase) pair.
    """
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
        return pallas_rdma.fused_rdma_step(
            v, filt, (2, 2), "zero", quantize=True, interpret=params)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    want = oracle.run_serial_u8(x[0].astype(np.uint8), filt, 1)
    np.testing.assert_array_equal(np.asarray(out)[0].astype(np.uint8), want)


def test_rdma_back_to_back_race(grey_small):
    """≥2 chained invocations under the race detector (cross-invocation fix).

    The iteration driver runs the kernel back-to-back inside a fori_loop;
    the start-of-kernel neighbor barrier must keep a fast device's
    iteration-N+1 remote copies out of a slow neighbor's still-live
    iteration-N scratch.  detect_races=True checks every (device, phase)
    pair across all three chained invocations, and the result must stay
    bit-exact vs three serial oracle steps.
    """
    import jax.numpy as jnp
    from jax import lax
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
        def one(_, cur):
            return pallas_rdma.fused_rdma_step(
                cur, filt, (2, 2), "zero", quantize=True, interpret=params)
        return lax.fori_loop(0, 3, one, v)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    want = oracle.run_serial_u8(x[0].astype(np.uint8), filt, 3)
    np.testing.assert_array_equal(np.asarray(out)[0].astype(np.uint8), want)
    assert jnp.issubdtype(out.dtype, jnp.floating)


def test_collective_id_registry():
    from parallel_convolution_tpu.ops import collective_ids

    assert collective_ids.collective_id("rdma_halo_stencil") == 1
    with pytest.raises(KeyError, match="no collective_id"):
        collective_ids.collective_id("nope")
    # ids must be unique — a collision in the static table is a code bug
    ids = list(collective_ids._COLLECTIVE_IDS.values())
    assert len(ids) == len(set(ids))


def _run_rdma_tiled(img, filt, iters, mesh_shape, tile=None, tiled=True,
                    boundary="zero", pad_operand=None, fuse=1,
                    storage=np.float32):
    from jax.sharding import PartitionSpec as P

    from parallel_convolution_tpu.ops import pallas_rdma
    from parallel_convolution_tpu.parallel.mesh import AXES

    mesh = _mesh(mesh_shape)
    x = imageio.interleaved_to_planar(img).astype(storage)
    valid_hw = None if boundary == "periodic" else img.shape[:2]

    def body(v):
        def one(_, cur):
            return pallas_rdma.fused_rdma_step(
                cur, filt, mesh_shape, boundary, quantize=True,
                tiled=tiled, tile=tile, pad_operand=pad_operand,
                fuse=fuse, valid_hw=valid_hw)
        import jax.lax as lax

        return lax.fori_loop(0, iters, one, v)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(None, *AXES), out_specs=P(None, *AXES),
        check_vma=False,
    ))(x)
    return np.asarray(out)[0].astype(np.uint8)


def test_rdma_tiled_bitexact_corners():
    """Forced-tiled variant: multi-window grid, 2 chained iterations, 2×2
    mesh — corners must propagate through the aligned-band two-phase
    exchange and match the oracle bit-for-bit."""
    filt = filters.get_filter("blur3")
    # per-device block 32x128 with tile (16, 128): 2x1 window grid per
    # block, plus chained invocations through the neighbor barrier
    img = imageio.generate_test_image(64, 256, "grey", seed=21)
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128))
    want = oracle.run_serial_u8(img, filt, 2)
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_pad_operand_bitexact():
    """Operand-backed HBM pad (the discarded-second-output form the
    compiled path uses in place of an HBM scratch entry): same bytes as
    the scratch form and as the oracle, through chained iterations with
    corner propagation."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(64, 256, "grey", seed=23)
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128),
                          pad_operand=True)
    want = oracle.run_serial_u8(img, filt, 2)
    np.testing.assert_array_equal(got, want)
    scratch_form = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128),
                                   pad_operand=False)
    np.testing.assert_array_equal(got, scratch_form)


def test_rdma_tiled_pad_operand_periodic():
    """Operand mode under the torus: self-wrap axes fill ghosts by local
    aligned copies; the zero-filled operand must not leak through."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(32, 256, "grey", seed=24)
    got = _run_rdma_tiled(img, filt, 2, (1, 2), tile=(16, 128),
                          boundary="periodic", pad_operand=True)
    want = oracle.run_serial_u8(img, filt, 2, boundary="periodic")
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_gaussian5_radius2():
    """radius-2 ghost bands through the tiled exchange (2-hop corners)."""
    filt = filters.get_filter("gaussian5")
    img = imageio.generate_test_image(64, 256, "grey", seed=22)
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128))
    want = oracle.run_serial_u8(img, filt, 2)
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_periodic_wrap():
    """Periodic torus incl. a self-wrap axis (1×2 grid: R==1 wraps to
    itself via local band copies, Cc==2 via remote bands)."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(32, 256, "grey", seed=23)
    got = _run_rdma_tiled(img, filt, 2, (1, 2), tile=(16, 128),
                          boundary="periodic")
    want = oracle.run_serial_u8(img, filt, 2, boundary="periodic")
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_non_dividing_tile():
    """Tile that does not divide the block: the last window row/col of
    the grid covers pad-rim garbage, which the valid-box mask must zero
    — bit-exactness across 2 chained iterations proves it."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(96, 384, "grey", seed=25)
    # blocks 48x192 per device; tile (32, 128) -> 2x2 windows with a
    # 16-row / 64-col rim beyond the block in the last row/col windows
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(32, 128))
    want = oracle.run_serial_u8(img, filt, 2)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", range(4))
def test_rdma_tiled_geometry_fuzz(seed):
    """Seeded random geometries through the tiled kernel: block shapes
    (aligned and ragged), tile sizes, mesh aspects, radii — every combo
    must stay bit-exact vs the oracle.  Catches mask/band geometry bugs
    the hand-picked cases might miss."""
    rng = np.random.default_rng(100 + seed)
    mesh_shape = [(2, 2), (1, 2), (2, 1)][int(rng.integers(3))]
    R, Cc = mesh_shape
    # blocks must satisfy the tiled guard: h >= sublane(8 f32), w >= 128
    bh = int(rng.integers(8, 40))
    bw = 128 + int(rng.integers(0, 130))
    rows, cols = bh * R, bw * Cc
    filt = filters.get_filter(["blur3", "gaussian5"][int(rng.integers(2))])
    tile = (int(rng.integers(1, 5)) * 8, 128)
    iters = int(rng.integers(1, 3))
    img = imageio.generate_test_image(rows, cols, "grey",
                                      seed=int(rng.integers(1000)))
    got = _run_rdma_tiled(img, filt, iters, mesh_shape, tile=tile)
    want = oracle.run_serial_u8(img, filt, iters)
    np.testing.assert_array_equal(got, want)


def test_rdma_auto_tiles_beyond_vmem_bound():
    """Blocks beyond the monolithic kernel's VMEM budget auto-select the
    tiled variant.  1664×1792 f32 block → 12.3 MB padded f32 + 11.9 MB out >
    the 10 MB budget; the whole-block-in-VMEM kernel could not hold it.
    One step on a 2×1 mesh, bit-exact vs the oracle."""
    from parallel_convolution_tpu.ops import pallas_rdma

    C, h, w = 1, 1664, 1792
    mono = C * (h + 2) * (w + 2) * 4 + C * h * w * 4
    assert mono > pallas_rdma._TILED_VMEM_BYTES

    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(2 * h, w, "grey", seed=24)
    got = _run_rdma_tiled(img, filt, 1, (2, 1), tiled=None)  # auto
    want = oracle.run_serial_u8(img, filt, 1)
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_rejects_sub_band_blocks():
    """Blocks narrower than one transfer band would self-overlap the
    band copies (undefined on real DMA engines) — must be rejected."""
    import jax.numpy as jnp

    from parallel_convolution_tpu.ops import pallas_rdma

    small = jnp.zeros((1, 8, 64), jnp.float32)
    with pytest.raises(ValueError, match="non-overlapping band"):
        pallas_rdma.fused_rdma_step(small, filters.get_filter("blur3"),
                                    (2, 2), tiled=True)


def test_rdma_auto_untileable_raises():
    """Over-VMEM-budget block + radius too big for aligned bands must be
    a clear error, not a silent fall-through to a Mosaic VMEM failure."""
    import jax.numpy as jnp

    from parallel_convolution_tpu.ops import pallas_rdma

    big = jnp.zeros((1, 2048, 2048), jnp.float32)
    wide = filters.gaussian(19, 3.0)  # r=9 > f32 sublane (8)
    with pytest.raises(ValueError, match="use a finer"):
        pallas_rdma.fused_rdma_step(big, wide, (2, 2))


# ---------------------------------------------------------------------------
# Temporal fusion (fuse=T) inside the RDMA kernels: exchange once, iterate
# T levels in-kernel.  Parity contract: bit-exact vs the serial oracle for
# T single-exchange iterations — both kernels, both boundaries, f32 + u8.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fuse", [2, 4])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_rdma_fused_bitexact_monolithic(fuse, boundary):
    """Monolithic kernel, 2x2 CPU mesh: T*r-deep exchange + T in-kernel
    levels must match 8 oracle iterations byte-for-byte.  Zero boundary
    uses awkward odd dims (pad-to-multiple rim -> per-level global-image
    re-masking); periodic uses mesh-divisible dims (required)."""
    filt = filters.get_filter("blur3")
    if boundary == "periodic":
        img = imageio.generate_test_image(32, 48, "grey", seed=31)
    else:
        img = imageio.generate_test_image(37, 53, "grey", seed=31)
    want = oracle.run_serial_u8(img, filt, 8, boundary=boundary)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    out = step.sharded_iterate(x, filt, 8, mesh=_mesh((2, 2)), quantize=True,
                               backend="pallas_rdma", boundary=boundary,
                               fuse=fuse)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_fused_u8_storage(grey_odd):
    """fuse=2 through the driver with the u8 iteration carry."""
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 6)
    out = step.sharded_iterate(
        imageio.interleaved_to_planar(grey_odd).astype(np.float32),
        filt, 6, mesh=_mesh((2, 2)), quantize=True, backend="pallas_rdma",
        storage="u8", fuse=2)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_rdma_fused_remainder_path(grey_odd):
    """7 iters at fuse=3 -> two fused chunks + a single-step tail, all
    through the RDMA kernel."""
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 7)
    out = step.sharded_iterate(
        imageio.interleaved_to_planar(grey_odd).astype(np.float32),
        filt, 7, mesh=_mesh((2, 2)), quantize=True, backend="pallas_rdma",
        fuse=3)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fuse", [2, 4])
def test_rdma_tiled_fused_bitexact(fuse):
    """Tiled kernel, 2x2 mesh: the sub_v/128-deep aligned bands carry
    r*T live ghost rows/cols; 2 chained fused chunks must equal 2*T
    oracle iterations."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(64, 256, "grey", seed=26)
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128), fuse=fuse)
    want = oracle.run_serial_u8(img, filt, 2 * fuse)
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_fused_periodic():
    """Tiled fuse=2 on the torus incl. a self-wrap axis."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(32, 256, "grey", seed=27)
    got = _run_rdma_tiled(img, filt, 2, (1, 2), tile=(16, 128),
                          boundary="periodic", fuse=2)
    want = oracle.run_serial_u8(img, filt, 4, boundary="periodic")
    np.testing.assert_array_equal(got, want)


def test_rdma_tiled_fused_u8():
    """Tiled fuse through a u8 carry (sublane 32: one band holds 8 live
    ghost rows with room to spare) on a multi-window grid."""
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(64, 256, "grey", seed=28)
    got = _run_rdma_tiled(img, filt, 2, (2, 2), tile=(16, 128), fuse=4,
                          storage=np.uint8)
    want = oracle.run_serial_u8(img, filt, 8)
    np.testing.assert_array_equal(got, want)


# --- Degenerate grids: extent-1 axes statically elide every RDMA
# construct, so these run under ANY jax (no faithful interpreter needed)
# and pin the fused compute path — per-level masking, quantize threading,
# shrink geometry — on both kernels.


@pytest.mark.parametrize("fuse", [2, 4])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_rdma_fused_degenerate_monolithic(fuse, boundary):
    filt = filters.get_filter("blur3")
    dims = (24, 36) if boundary == "periodic" else (37, 53)
    img = imageio.generate_test_image(*dims, "grey", seed=33)
    want = oracle.run_serial_u8(img, filt, 8, boundary=boundary)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    out = step.sharded_iterate(x, filt, 8, mesh=_mesh((1, 1)), quantize=True,
                               backend="pallas_rdma", boundary=boundary,
                               fuse=fuse)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fuse", [2, 4])
def test_rdma_fused_degenerate_tiled(fuse):
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(96, 384, "grey", seed=34)
    # tile (32, 128) does not divide the 96x384 block: the window-rim
    # garbage must die in the tier-1 select before the level loop
    got = _run_rdma_tiled(img, filt, 2, (1, 1), tile=(32, 128), fuse=fuse)
    want = oracle.run_serial_u8(img, filt, 2 * fuse)
    np.testing.assert_array_equal(got, want)


def test_rdma_fused_degenerate_tiled_u8_radius2():
    filt = filters.get_filter("gaussian5")
    img = imageio.generate_test_image(64, 256, "grey", seed=35)
    # r=2, fuse=4 -> d=8; u8 sublane is 32, so one band still carries it
    got = _run_rdma_tiled(img, filt, 1, (1, 1), tile=(32, 128), fuse=4,
                          storage=np.uint8)
    want = oracle.run_serial_u8(img, filt, 4)
    np.testing.assert_array_equal(got, want)


def _separable_cases():
    # Tiled: r*fuse must fit one (sublane, 128) band (bf16 16, u8 32).
    sublane = {"bf16": 16, "u8": 32}
    for tiled in (False, True):
        for name, r in (("blur3", 1), ("gaussian5", 2)):
            for fuse in (1, 4, 16):
                for storage in ("u8", "bf16"):
                    if tiled and r * fuse > sublane[storage]:
                        continue
                    for boundary in ("zero", "periodic"):
                        for image in ("noise", "checker"):
                            yield pytest.param(
                                tiled, name, fuse, storage, boundary, image,
                                id=f"{'tiled' if tiled else 'monolithic'}-"
                                   f"{name}-fuse{fuse}-{storage}-{boundary}-"
                                   f"{image}")


@pytest.mark.parametrize("tiled,name,fuse,storage,boundary,image",
                         _separable_cases())
def test_rdma_separable_form_degenerate(tiled, name, fuse, storage,
                                        boundary, image, monkeypatch):
    """Rank-1 dyadic filters in quantize mode run the separable form in
    both RDMA kernels; two chained launches equal the oracle's bytes."""
    import ml_dtypes

    from parallel_convolution_tpu.ops import pallas_rdma

    forms = []   # the tap form of every level loop the kernels trace
    run_levels = pallas_rdma._iterate_levels

    def levels(cur, *, form, **kw):
        forms.append(form.name)
        return run_levels(cur, form=form, **kw)

    monkeypatch.setattr(pallas_rdma, "_iterate_levels", levels)
    jax.clear_caches()   # retrace the jitted step, so the spy sees it
    filt = filters.get_filter(name)
    if image == "noise":
        img = np.random.default_rng(fuse).integers(0, 256, (64, 256),
                                                   dtype=np.uint8)
    else:
        img = (255 * ((np.arange(64)[:, None] + np.arange(256)) % 2)).astype(
            np.uint8)
    dtype = {"u8": np.uint8, "bf16": ml_dtypes.bfloat16}[storage]
    got = _run_rdma_tiled(img, filt, 2, (1, 1), tile=(32, 128),
                          tiled=tiled, boundary=boundary, fuse=fuse,
                          storage=dtype)
    want = oracle.run_serial_u8(img, filt, 2 * fuse, boundary=boundary)
    np.testing.assert_array_equal(got, want)
    assert forms and set(forms) == {"separable"}


# --- Constraint surface: the fuse guards that replaced the old
# fuse=1-only ValueError.


def test_rdma_fuse_guard_gone():
    # Building a fused RDMA step is now legal (the old guard raised here).
    step._make_block_step(filters.get_filter("blur3"), (2, 2), (16, 16),
                          (8, 8), True, "pallas_rdma", fuse=2)


def test_rdma_fuse_depth_exceeds_block():
    import jax.numpy as jnp

    from parallel_convolution_tpu.ops import pallas_rdma

    with pytest.raises(ValueError, match="ghost depth"):
        pallas_rdma.fused_rdma_step(jnp.zeros((1, 8, 8), jnp.float32),
                                    filters.get_filter("blur3"), (2, 2),
                                    fuse=9, valid_hw=(16, 16))


def test_rdma_tiled_fuse_depth_exceeds_band():
    import jax.numpy as jnp

    from parallel_convolution_tpu.ops import pallas_rdma

    # f32 sublane is 8: r*fuse = 9 live ghosts cannot ride one band
    with pytest.raises(ValueError, match="r\\*fuse"):
        pallas_rdma.fused_rdma_step(jnp.zeros((1, 64, 256), jnp.float32),
                                    filters.get_filter("blur3"), (2, 2),
                                    tiled=True, fuse=9, valid_hw=(128, 512))


def test_rdma_fused_needs_valid_hw():
    import jax.numpy as jnp

    from parallel_convolution_tpu.ops import pallas_rdma

    with pytest.raises(ValueError, match="valid_hw"):
        pallas_rdma.fused_rdma_step(jnp.zeros((1, 32, 32), jnp.float32),
                                    filters.get_filter("blur3"), (2, 2),
                                    fuse=2)


@pytest.mark.parametrize("storage,fuse", [("bf16", 4), ("u8", 8)])
def test_rdma_run_planar_pads_unaligned_rgb_blocks(storage, fuse):
    """The normal path on a 2x2 mesh whose 32x140 RGB blocks are not whole
    (sublane, 128) tiles: the step pads each block to 32x256, the valid
    box keeps the rim out, and the bytes are the oracle's."""
    from parallel_convolution_tpu.models import ConvolutionModel

    img = np.random.default_rng(25).integers(0, 256, (64, 280, 3),
                                             dtype=np.uint8)
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(img, filt, 10)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    model = ConvolutionModel(filt, mesh=_mesh((2, 2)), backend="pallas_rdma",
                             storage=storage, fuse=fuse, quantize=True)
    out = model.run_planar(x, 10)
    assert out.shape == (3, 64, 280)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("storage", ["f32", "bf16", "u8"])
def test_prepare_aligns_only_the_rdma_block(storage):
    """Only the form that moves whole tiles by DMA gets tile-aligned
    blocks; every other form keeps the mesh's own share."""
    x = np.zeros((3, 64, 280), np.float32)
    m = _mesh((2, 2))
    filt = filters.get_filter("blur3")
    sub_v = {"f32": 8, "bf16": 16, "u8": 32}[storage]

    def prepare(backend):
        return step._prepare(x, m, step.resolve_launch(
            m, filt, x.shape, backend=backend, storage=storage))

    for backend in ("pallas_sep", "pallas", "shifted"):
        xs, valid, block = prepare(backend)
        assert block == (32, 140) and xs.shape == (3, 64, 280)
    xs, valid, block = prepare("pallas_rdma")
    assert valid == (64, 280)
    assert block == (-(-32 // sub_v) * sub_v, 256)
    assert xs.shape == (3, 2 * block[0], 512)
    # A torus keeps its wrap: no padding beyond the mesh's share.
    assert step.block_extent((64, 280), (2, 2), "pallas_rdma", storage,
                             "periodic") == (32, 140)
