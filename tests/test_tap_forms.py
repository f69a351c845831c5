"""The kernels' static tap forms: which filters take the box-plus-sparse
form, and that every form a kernel builds keeps the oracle's bytes.

The box-plus-sparse form runs a k×k filter as one tap value ``c`` times
a unit-weight box sum plus one multiply-add per tap that differs from
``c``.  It leaves the normative op order, so it is admitted only where
every partial sum is exact (quantize mode, dyadic taps, the 2²⁴
bound); these tests hold it to the oracle there, with the clip engaged
at both ends, on multi-tile grids with a ragged edge.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from parallel_convolution_tpu.models import ConvolutionModel
from parallel_convolution_tpu.ops import filters, oracle, pallas_stencil
from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.parallel import step
from parallel_convolution_tpu.parallel.mesh import make_grid_mesh

BOX_SPARSE = ["edge3", "edge5", "sharpen3", "jacobi3", "identity3"]
STORAGE = {"u8": jnp.uint8, "bf16": jnp.bfloat16}
HW = (40, 300)      # a (16, 128) tile grid of 3 x 3, ragged on both axes
TILE = (16, 128)


def _image(kind):
    H, W = HW
    if kind == "noise":
        return np.random.default_rng(24).integers(0, 256, HW).astype(
            np.uint8)
    if kind == "white":
        return np.full(HW, 255, np.uint8)
    # 0/255 checkerboard: edge filters push it past both clip ends.
    return (255 * ((np.arange(H)[:, None] + np.arange(W)) % 2)).astype(
        np.uint8)


def _filter(name):
    # gaussian(3, 1.0): an exact rank-1 factorization with non-dyadic taps.
    if name == "gaussian3_sigma1":
        return filters.gaussian(3, 1.0)
    return filters.get_filter(name)


# --------------------------------------------------------------------------
# The static plan
# --------------------------------------------------------------------------


def test_edge5_plan_is_minus_box_plus_centre():
    assert pallas_stencil._box_sparse_taps(
        filters.get_filter("edge5"), True) == (-1.0, ((2, 2, 25.0),))


@pytest.mark.parametrize("name,c,n_residue", [
    ("edge3", -1.0, 1), ("edge5", -1.0, 1), ("sharpen3", 0.0, 5),
    ("jacobi3", 0.0, 4), ("identity3", 0.0, 1)])
def test_registry_plans(name, c, n_residue):
    filt = filters.get_filter(name)
    form = pallas_stencil.tap_form(filt, False, True)
    assert form.name == "box_sparse"
    got_c, residue = form.args
    assert (got_c, len(residue)) == (c, n_residue)
    # The plan reproduces the taps exactly.
    k = filt.size
    taps = np.full((k, k), c)
    for dy, dx, rv in residue:
        taps[dy, dx] += rv
    np.testing.assert_array_equal(taps, filt.taps)


@pytest.mark.parametrize("name", ["blur3", "gaussian5"])
def test_separable_filters_keep_the_separable_form(name):
    filt = filters.get_filter(name)
    assert pallas_stencil.tap_form(filt, True, True).name == "separable"
    # Not requested: the box form would take more slices than k² taps.
    assert pallas_stencil.tap_form(filt, False, True).name == "direct"


@pytest.mark.parametrize("filt,quantize", [
    (filters.make_filter("edge5_custom", filters.EDGE5.taps), True),
    (filters.gaussian(5, 0.7), True),
    (filters.get_filter("edge5"), False),
    (Filter("coarse", np.where(np.eye(3), 1 / 512, -1.0), dyadic=True),
     True),
    (Filter("steep", np.pad([[300.0]], 2, constant_values=-1.0),
            dyadic=True), True),
], ids=["make_filter", "gaussian_0.7", "float_mode", "finer_than_1/256",
        "over_2^24"])
def test_outside_the_exact_regime_the_direct_loop_stays(filt, quantize):
    assert pallas_stencil.tap_form(filt, False, quantize).name == "direct"
    assert pallas_stencil.tap_form(filt, True, quantize).name == "direct"


@pytest.mark.parametrize("filt,quantize,want", [
    (filters.BLUR3, True, True),
    (filters.GAUSSIAN5, True, True),
    (filters.BLUR3, False, False),
    (filters.gaussian(3, 1.0), True, False),
    (filters.SHARPEN3, True, False),
    # Factors [1, 2, 1]/32: on the 1/256 grid, though the taps are not.
    (Filter("fine", np.outer([1, 2, 1], [1, 2, 1]) / 1024, dyadic=True),
     True, True),
    # Factors [8, 16, 8]: past the 2^24 bound on the 1/256 grid.
    (Filter("steep", np.outer([1, 2, 1], [1, 2, 1]) * 64.0, dyadic=True),
     True, False),
], ids=["blur3", "gaussian5", "float_mode", "non_dyadic", "not_rank1",
        "fine_factors", "over_2^24"])
def test_rank1_exact(filt, quantize, want):
    assert pallas_stencil.rank1_exact(filt, quantize) is want


@pytest.mark.parametrize("backend,name,quantize,want", [
    ("pallas_sep", "edge5", True, "box_sparse"),
    ("pallas_sep", "blur3", True, "separable"),
    ("pallas", "edge5", True, "box_sparse"),
    ("pallas", "blur3", True, "direct"),
    ("pallas_rdma", "sharpen3", True, "box_sparse"),
    ("pallas_rdma", "blur3", True, "separable"),
    ("pallas_rdma", "gaussian5", True, "separable"),
    ("pallas_rdma", "blur3", False, "direct"),
    ("pallas_rdma", "gaussian3_sigma1", True, "direct"),
    ("pallas_sep", "edge5", False, "direct"),
    ("shifted", "edge5", True, "direct"),
    ("separable", "blur3", True, "separable"),
    ("xla_conv", "blur3", True, "direct"),
])
def test_runner_keeps_its_tap_form(backend, name, quantize, want):
    mesh = make_grid_mesh(jax.devices()[:1], (1, 1))
    fn = step._build_iterate(mesh, _filter(name), 4, quantize,
                             (16, 128), (16, 128), backend, 2)
    assert fn.tap_form == want


def _build_spans(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = [(e.start_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "pctpu.step.build"]
    return [stats for _, stats in sorted(spans, key=lambda s: s[0])]


def test_build_span_names_the_tap_form(tmp_path):
    x = np.random.default_rng(5).integers(0, 256, (1, 16, 128)).astype(
        np.float32)
    mesh = make_grid_mesh(jax.devices()[:1], (1, 1))
    models = [ConvolutionModel(name, mesh=mesh, backend="pallas_sep",
                               storage="bf16", fuse=2)
              for name in ("edge5", "blur3")]
    for m in models:
        m.run_planar(x, 4).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for m in models:
            m.run_planar(x, 4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert [s["tap_form"] for s in _build_spans(tmp_path)] == [
        "box_sparse", "separable"]


def test_rdma_build_span_names_the_separable_form(tmp_path):
    x = np.random.default_rng(26).integers(0, 256, (1, 32, 128)).astype(
        np.float32)
    mesh = make_grid_mesh(jax.devices()[:1], (1, 1))
    models = [ConvolutionModel(name, mesh=mesh, backend="pallas_rdma",
                               storage="u8", fuse=2)
              for name in ("blur3", "sharpen3")]
    for m in models:
        m.run_planar(x, 4).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for m in models:
            m.run_planar(x, 4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert [s["tap_form"] for s in _build_spans(tmp_path)] == [
        "separable", "box_sparse"]


# --------------------------------------------------------------------------
# Bit-exactness against the oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("image", ["noise", "white", "checker"])
@pytest.mark.parametrize("storage", sorted(STORAGE))
@pytest.mark.parametrize("name", BOX_SPARSE)
def test_unfused_kernel_matches_oracle(name, storage, image):
    filt = filters.get_filter(name)
    img = _image(image)
    x = jnp.asarray(img[None], STORAGE[storage])
    got = pallas_stencil.correlate_shifted_pallas(x, filt, quantize=True,
                                                  tile=TILE)
    np.testing.assert_array_equal(
        np.asarray(got[0].astype(jnp.float32)).astype(np.uint8),
        oracle.convolve_once_u8(img, filt))


@pytest.mark.parametrize("image", ["noise", "white", "checker"])
@pytest.mark.parametrize("T", [1, 8])
@pytest.mark.parametrize("storage", sorted(STORAGE))
@pytest.mark.parametrize("name", BOX_SPARSE)
def test_fused_kernel_matches_oracle(name, storage, T, image):
    filt = filters.get_filter(name)
    img = _image(image)
    d = filt.radius * T
    padded = jnp.pad(jnp.asarray(img[None], STORAGE[storage]),
                     ((0, 0), (d, d), (d, d)))
    got = pallas_stencil.fused_iterate_pallas(
        padded, jnp.zeros((2,), jnp.int32), filt, T, HW, tile=TILE,
        quantize=True)
    np.testing.assert_array_equal(
        np.asarray(got[0].astype(jnp.float32)).astype(np.uint8),
        oracle.run_serial_u8(img, filt, T))


@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_edge5_on_a_2x2_mesh_matches_oracle(boundary):
    img = _image("noise")[:32, :256]
    filt = filters.get_filter("edge5")
    mesh = make_grid_mesh(jax.devices()[:4], (2, 2))
    got = step.sharded_iterate(
        img[None].astype(np.float32), filt, 10, mesh, quantize=True,
        backend="pallas_sep", storage="bf16", fuse=4, boundary=boundary)
    np.testing.assert_array_equal(
        np.asarray(got[0]).astype(np.uint8),
        oracle.run_serial_u8(img, filt, 10, boundary))
