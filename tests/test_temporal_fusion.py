"""Temporal fusion (fuse=T): T iterations per halo exchange, bit-exact."""

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.utils import imageio


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]], shape)


def _run(img, filt, iters, mshape, **kw):
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    out = step.sharded_iterate(x, filt, iters, mesh=_mesh(mshape),
                               quantize=True, **kw)
    return imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))


@pytest.mark.parametrize("fuse", [2, 3, 5])
def test_fused_bitexact_vs_oracle(grey_odd, fuse):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 10)
    got = _run(grey_odd, filt, 10, (2, 4), fuse=fuse)
    np.testing.assert_array_equal(got, want)


def test_fused_remainder_path(grey_odd):
    # 7 iters, fuse 3 -> chunks 3+3 then tail of 1
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 7)
    got = _run(grey_odd, filt, 7, (2, 2), fuse=3)
    np.testing.assert_array_equal(got, want)


def test_fused_radius2_rgb(rgb_odd):
    filt = filters.get_filter("gaussian5")
    want = oracle.run_serial_u8(rgb_odd, filt, 4)
    got = _run(rgb_odd, filt, 4, (2, 2), fuse=2)
    np.testing.assert_array_equal(got, want)


def test_fused_bf16(grey_odd):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 8)
    got = _run(grey_odd, filt, 8, (2, 4), fuse=4, storage="bf16")
    np.testing.assert_array_equal(got, want)


def test_fused_pallas_backend(grey_odd):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 6)
    got = _run(grey_odd, filt, 6, (2, 2), fuse=3, backend="pallas")
    np.testing.assert_array_equal(got, want)


def test_fuse_too_deep_raises(grey_small):
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    with pytest.raises(ValueError, match="fuse"):
        # 24-row image on 8-row grid -> 3-row blocks; fuse=20 needs 20-deep
        step.sharded_iterate(x, filt, 40, mesh=_mesh((8, 1)), fuse=20)


def _slab_depths(fn, xs):
    """Halo-slab depths of every collective-permute in ``fn``'s HLO."""
    import re

    hlo = fn.lower(xs).compile().as_text()
    shapes = re.findall(
        r"f32\[1,(\d+),(\d+)\][^\n]*collective-permute", hlo
    )
    assert shapes, "no collective-permute in HLO"
    return {min(int(a), int(b)) for a, b in shapes}


def test_fused_halo_exchanges_deep_slabs(grey_small):
    # fuse=5 must exchange 5-deep halo slabs once per chunk (1/5 the
    # collective rounds of fuse=1, whose slabs are 1-deep).
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, block_hw = step._prepare(
        x, m, step.resolve_launch(m, filt, x.shape))

    def depths(fuse):
        return _slab_depths(step._build_iterate(
            m, filt, 10, True, valid_hw, block_hw, "shifted", fuse), xs)

    assert depths(1) == {1}
    assert depths(5) == {5}


def test_fused_convergence_exchanges_deep_slabs(grey_small):
    """The round-4 fused convergence path must carry the same structural
    saving: inside the while_loop chunk, fused steps exchange fuse-deep
    slabs (one collective round per fuse iterations) — asserted in the
    compiled HLO, no silicon needed."""
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, block_hw = step._prepare(
        x, m, step.resolve_launch(m, filt, x.shape))

    def depths(fuse):
        return _slab_depths(step._build_converge(
            m, filt, 0.5, 40, 10, True, valid_hw, block_hw, "shifted",
            "zero", fuse), xs)

    assert depths(1) == {1}
    # Fused program contains BOTH depths: 4-deep slabs in the fused
    # fori_loop plus 1-deep in the remainder/pair-forming single steps.
    assert depths(4) == {1, 4}


@pytest.mark.parametrize("fuse", [2, 4])
@pytest.mark.parametrize("storage", ["f32", "bf16"])
def test_fused_pallas_kernel_bitexact(grey_odd, fuse, storage):
    # The in-VMEM multi-level kernel path (backend=pallas, fuse>1).
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 8)
    got = _run(grey_odd, filt, 8, (2, 4), fuse=fuse, backend="pallas",
               storage=storage)
    np.testing.assert_array_equal(got, want)


def test_fused_pallas_kernel_rgb_gaussian5(rgb_odd):
    filt = filters.get_filter("gaussian5")
    want = oracle.run_serial_u8(rgb_odd, filt, 4)
    got = _run(rgb_odd, filt, 4, (2, 2), fuse=2, backend="pallas")
    np.testing.assert_array_equal(got, want)


def test_fused_pallas_kernel_float_mode():
    filt = filters.get_filter("jacobi3")
    img = imageio.generate_test_image(32, 40, "grey", seed=41)
    want = oracle.run_serial_f32(img.astype(np.float32), filt, 6)
    x = img[None].astype(np.float32)
    out = step.sharded_iterate(x, filt, 6, mesh=_mesh((2, 2)),
                               quantize=False, backend="pallas", fuse=3)
    np.testing.assert_array_equal(np.asarray(out)[0], want)


def test_fused_pallas_multi_tile():
    # Block large enough to need a multi-tile pallas grid inside shard_map.
    filt = filters.get_filter("blur3")
    img = imageio.generate_test_image(48, 300, "grey", seed=42)
    want = oracle.run_serial_u8(img, filt, 4)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    from parallel_convolution_tpu.ops import pallas_stencil
    old = pallas_stencil.DEFAULT_TILE
    pallas_stencil.DEFAULT_TILE = (16, 128)
    try:
        out = step._build_iterate.__wrapped__(
            _mesh((2, 2)), filt, 4, True, (48, 300), (24, 150), "pallas", 2
        )(step._prepare(x, _mesh((2, 2)),
                        step.resolve_launch(_mesh((2, 2)), filt,
                                            x.shape))[0])
    finally:
        pallas_stencil.DEFAULT_TILE = old
    got = np.asarray(out)[:, :48, :300].astype(np.uint8)
    np.testing.assert_array_equal(got[0], want)
