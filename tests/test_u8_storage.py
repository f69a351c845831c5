"""u8 storage tier: the reference's own ``unsigned char`` carry dtype.

Quantized states are exact integers <= 255, so uint8 carries between
iterations lose nothing while quartering HBM/ICI traffic vs f32 (and
halving vs bf16) — accumulation stays f32 inside every correlate
implementation.  All paths must remain bit-identical to the serial oracle
(reference validation contract, SURVEY.md §4 golden-output comparison).
"""

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.utils import imageio


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]], shape)


@pytest.mark.parametrize("backend", ["shifted", "xla_conv", "separable",
                                     "pallas", "pallas_sep"])
def test_u8_bitexact_quantized(grey_odd, backend):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 6)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 6, mesh=_mesh((2, 4)),
                               quantize=True, backend=backend, storage="u8")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2), (4, 2), (1, 8)])
def test_u8_mesh_shapes(grey_odd, mesh_shape):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 4)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 4, mesh=_mesh(mesh_shape),
                               quantize=True, storage="u8")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_u8_rgb_radius2(rgb_odd):
    # radius-2 filter exercises the 2-deep halo exchange on u8 carries
    filt = filters.get_filter("gaussian5")
    want = oracle.run_serial_u8(rgb_odd, filt, 3)
    x = imageio.interleaved_to_planar(rgb_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 3, mesh=_mesh((2, 2)),
                               quantize=True, storage="u8")
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fuse", [2, 3])
def test_u8_temporal_fusion(grey_odd, fuse):
    # fused Pallas path: u8 HBM windows, f32 VMEM intermediates, u8 out
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 6)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    out = step.sharded_iterate(x, filt, 6, mesh=_mesh((2, 2)),
                               quantize=True, backend="pallas_sep",
                               storage="u8", fuse=fuse)
    got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
    np.testing.assert_array_equal(got, want)


def test_u8_periodic(grey_small):
    # 24x36 divides a 2x2 grid exactly -> torus wrap is legal
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    a = step.sharded_iterate(x, filt, 4, mesh=_mesh((2, 2)), quantize=True,
                             storage="u8", boundary="periodic")
    b = step.sharded_iterate(x, filt, 4, mesh=_mesh((2, 2)), quantize=True,
                             storage="f32", boundary="periodic")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_u8_converge_matches_f32(grey_small):
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    out_a, it_a = step.sharded_converge(x, filt, tol=0.5, max_iters=300,
                                        check_every=5, mesh=_mesh((2, 2)),
                                        quantize=True, storage="u8")
    out_b, it_b = step.sharded_converge(x, filt, tol=0.5, max_iters=300,
                                        check_every=5, mesh=_mesh((2, 2)),
                                        quantize=True, storage="f32")
    assert it_a == it_b
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


def test_u8_requires_quantize(grey_small):
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    with pytest.raises(ValueError, match="quantize"):
        step.sharded_iterate(x, filters.get_filter("blur3"), 2,
                             mesh=_mesh((1, 1)), quantize=False, storage="u8")
    with pytest.raises(ValueError, match="quantize"):
        step.sharded_converge(x, filters.get_filter("blur3"), tol=0.5,
                              max_iters=5, mesh=_mesh((1, 1)),
                              quantize=False, storage="u8")


def test_u8_iterate_prepared_guard(grey_small):
    # the public zero-copy entry must enforce the same quantize guard
    filt = filters.get_filter("blur3")
    mesh = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, _ = step._prepare(
        x, mesh, step.resolve_launch(mesh, filt, x.shape, storage="u8"))
    with pytest.raises(ValueError, match="quantize"):
        step.iterate_prepared(xs, filt, 2, mesh, valid_hw, quantize=False)


def test_u8_config_validation():
    from parallel_convolution_tpu.utils.config import RunConfig

    with pytest.raises(ValueError, match="quantize"):
        RunConfig(rows=8, cols=8, storage="u8", quantize=False)
    cfg = RunConfig(rows=8, cols=8, storage="u8")
    assert cfg.storage == "u8"


def test_u8_model_api(grey_small):
    from parallel_convolution_tpu.models import ConvolutionModel

    m = ConvolutionModel(filt="blur3", mesh=_mesh((2, 2)), storage="u8")
    got = m.run_image(grey_small, 5)
    want = oracle.run_serial_u8(grey_small, filters.get_filter("blur3"), 5)
    np.testing.assert_array_equal(got, want)


def test_u8_nonconvex_filter_keeps_clip(grey_odd):
    # sharpen3 has negative taps (not convex) → the kernels must keep the
    # [0, 255] clamp; on real images sharpening over/undershoots, so this
    # exercises clipping being LIVE, not just present.
    filt = filters.get_filter("sharpen3")
    want = oracle.run_serial_u8(grey_odd, filt, 4)
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    for backend in ("pallas", "pallas_sep"):
        out = step.sharded_iterate(x, filt, 4, mesh=_mesh((2, 2)),
                                   quantize=True, backend=backend,
                                   storage="u8")
        got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
        np.testing.assert_array_equal(got, want)


def test_u8_convex_saturated_image_stays_in_range():
    # All-255 input through a NON-dyadic convex filter (gaussian taps do
    # not sum to exactly 1.0 in f32): the elided-clip path must still
    # produce bytes <= 255 — the convexity proof's boundary case.
    img = np.full((40, 56), 255, dtype=np.uint8)
    filt = filters.gaussian(5, 1.2)
    assert filt.convex and not filt.dyadic
    want = oracle.run_serial_u8(img, filt, 5)
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    for fuse in (1, 5):
        out = step.sharded_iterate(x, filt, 5, mesh=_mesh((2, 2)),
                                   quantize=True, backend="pallas_sep",
                                   storage="u8", fuse=fuse)
        got = imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))
        np.testing.assert_array_equal(got, want)


def test_quantize_contract_out_of_range_raises():
    # With a convex filter the store-back clamp is elided, so a
    # float plane outside [0, 255] must be rejected up front instead of
    # silently propagating unclamped.
    filt = filters.get_filter("blur3")
    x = np.full((1, 16, 24), 300.0, dtype=np.float32)
    with pytest.raises(ValueError, match="outside the u8 contract"):
        step.sharded_iterate(x, filt, 2, mesh=_mesh((2, 2)), quantize=True)
    with pytest.raises(ValueError, match="outside the u8 contract"):
        step.sharded_converge(x, filt, tol=0.5, max_iters=4, quantize=True,
                              mesh=_mesh((2, 2)))
    # Non-convex filters keep the live clamp -> unchanged behavior, no error.
    sharp = filters.get_filter("sharpen3")
    step.sharded_iterate(x, sharp, 1, mesh=_mesh((2, 2)), quantize=True)
    # In-contract input through a convex filter: untouched fast path.
    ok = np.full((1, 16, 24), 128.0, dtype=np.float32)
    step.sharded_iterate(ok, filt, 1, mesh=_mesh((2, 2)), quantize=True)
