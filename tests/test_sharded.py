"""Distributed semantics on the forced 8-device CPU backend.

The upgrade over the reference's test story (SURVEY.md §4): halo exchange,
corner propagation, non-divisible shapes and convergence reductions are all
exercised without a cluster, and outputs are required to be bit-identical to
the serial NumPy oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib
from parallel_convolution_tpu.parallel import step
from parallel_convolution_tpu.utils import imageio

MESH_SHAPES = [(1, 1), (1, 2), (2, 2), (4, 2), (2, 4), (8, 1), (1, 8)]


def _mesh(shape):
    n = shape[0] * shape[1]
    return mesh_lib.make_grid_mesh(jax.devices()[:n], shape)


def _run_sharded_u8(img_u8, filt, iters, mshape, backend="shifted"):
    x = imageio.interleaved_to_planar(img_u8).astype(np.float32)
    out = step.sharded_iterate(x, filt, iters, mesh=_mesh(mshape),
                               quantize=True, backend=backend)
    return imageio.planar_to_interleaved(np.asarray(out).astype(np.uint8))


def test_dims_create():
    assert mesh_lib.dims_create(8) == (2, 4)
    assert mesh_lib.dims_create(16) == (4, 4)
    assert mesh_lib.dims_create(7) == (1, 7)
    assert mesh_lib.dims_create(1) == (1, 1)
    assert mesh_lib.dims_create(12) == (3, 4)


@pytest.mark.parametrize("mshape", MESH_SHAPES)
def test_blur_bitexact_all_mesh_shapes(grey_odd, mshape):
    # 37×53 does not divide evenly by any of these grids → exercises padding
    # + masking alongside the halo exchange.
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_odd, filt, 5)
    got = _run_sharded_u8(grey_odd, filt, 5, mshape)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mshape", [(2, 2), (2, 4)])
def test_rgb_bitexact(rgb_odd, mshape):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(rgb_odd, filt, 4)
    got = _run_sharded_u8(rgb_odd, filt, 4, mshape)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["gaussian5", "edge5"])
def test_radius2_halo_bitexact(grey_odd, name):
    # 5×5 filters need 2-wide halos: corners require values two hops away,
    # the strongest test of two-phase corner propagation.
    filt = filters.get_filter(name)
    want = oracle.run_serial_u8(grey_odd, filt, 3)
    got = _run_sharded_u8(grey_odd, filt, 3, (2, 4))
    np.testing.assert_array_equal(got, want)


def test_xla_conv_backend_sharded(grey_small):
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_small, filt, 10)
    got = _run_sharded_u8(grey_small, filt, 10, (2, 2), backend="xla_conv")
    np.testing.assert_array_equal(got, want)


def test_sharded_equals_single_device(grey_odd):
    # property: shard(conv(x)) == conv(shard(x))
    filt = filters.get_filter("sharpen3")
    a = _run_sharded_u8(grey_odd, filt, 6, (1, 1))
    b = _run_sharded_u8(grey_odd, filt, 6, (4, 2))
    np.testing.assert_array_equal(a, b)


def test_hlo_contains_collective_permute(grey_small):
    # Guard against the halo silently materializing as all-gather
    # (SURVEY.md §2 'assert-in-HLO' requirement).
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, block_hw = step._prepare(
        x, m, step.resolve_launch(m, filt, x.shape))
    fn = step._build_iterate(m, filt, 3, True, valid_hw, block_hw, "shifted")
    hlo = fn.lower(xs).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo


def test_convergence_identity_immediate(grey_small):
    filt = filters.get_filter("identity3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    out, done = step.sharded_converge(x, filt, tol=1e-6, max_iters=100,
                                      check_every=4, mesh=_mesh((2, 2)))
    assert done == 4
    np.testing.assert_array_equal(
        imageio.planar_to_interleaved(np.asarray(out)), grey_small.astype(np.float32)
    )


def test_convergence_matches_oracle_jacobi():
    filt = filters.get_filter("jacobi3")
    img = imageio.generate_test_image(32, 48, "grey", seed=11).astype(np.float32)
    want, want_iters = oracle.run_to_convergence_f32(
        img, filt, tol=0.05, max_iters=500, check_every=10
    )
    x = img[None]
    got, got_iters = step.sharded_converge(
        x, filt, tol=0.05, max_iters=500, check_every=10, mesh=_mesh((2, 4))
    )
    assert got_iters == want_iters
    np.testing.assert_array_equal(np.asarray(got)[0], want)


@pytest.mark.parametrize("fuse,check_every", [(4, 10), (3, 10), (10, 10),
                                              (4, 3)])
def test_convergence_fused_matches_unfused(fuse, check_every):
    """fuse>1 in the convergence path: identical iters + bit-identical
    result for any (fuse, check_every) combination, including fuse >
    check_every (clamped) and non-divisible remainders."""
    filt = filters.get_filter("jacobi3")
    img = imageio.generate_test_image(32, 48, "grey", seed=3).astype(np.float32)
    x = img[None]
    want, want_iters = step.sharded_converge(
        x, filt, tol=0.05, max_iters=200, check_every=check_every,
        mesh=_mesh((2, 2)))
    got, got_iters = step.sharded_converge(
        x, filt, tol=0.05, max_iters=200, check_every=check_every,
        mesh=_mesh((2, 2)), fuse=fuse)
    assert got_iters == want_iters
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_convergence_fused_pallas_tile(grey_small):
    """Pallas backend + explicit tile through the convergence path."""
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    want, want_iters = step.sharded_converge(
        x, filt, tol=0.5, max_iters=60, check_every=5, mesh=_mesh((2, 2)),
        quantize=True)
    got, got_iters = step.sharded_converge(
        x, filt, tol=0.5, max_iters=60, check_every=5, mesh=_mesh((2, 2)),
        quantize=True, backend="pallas_sep", fuse=4, tile=(16, 128))
    assert got_iters == want_iters
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_iterate_tile_override_bit_identical(grey_odd):
    """sharded_iterate's public tile knob: any tile is bit-identical."""
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    want = step.sharded_iterate(x, filt, 3, mesh=_mesh((2, 2)),
                                backend="pallas")
    got = step.sharded_iterate(x, filt, 3, mesh=_mesh((2, 2)),
                               backend="pallas", tile=[8, 128])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="positive"):
        step.sharded_iterate(x, filt, 1, mesh=_mesh((2, 2)),
                             backend="pallas", tile=(0, 128))


def test_convergence_hits_max_iters(grey_small):
    # float-mode jacobi on noise shrinks diffs slowly: far from 1e-9 in 7
    # iterations, so the loop must run the full 3+3+1 chunk schedule.
    filt = filters.get_filter("jacobi3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    _, done = step.sharded_converge(x, filt, tol=1e-9, max_iters=7,
                                    check_every=3, mesh=_mesh((2, 2)),
                                    quantize=False)
    assert done == 7  # chunks of 3,3,1 — the min() remainder path


def test_block_smaller_than_radius_raises():
    filt = filters.get_filter("gaussian5")
    tiny = np.ones((1, 8, 3), np.float32)  # W blocks of 1 < radius 2 on 1×4
    with pytest.raises(ValueError, match="smaller than filter radius"):
        step.sharded_iterate(tiny, filt, 1, mesh=_mesh((1, 4)))


def test_mesh_interpret_resolves_from_mesh_devices():
    # One process can hold a TPU default backend AND a forced-CPU mesh
    # (the driver's entry() + dryrun_multichip sequence); interpret= must
    # come from the mesh's own devices, not jax.devices() — a CPU mesh
    # always interprets, and a TPU device never does.
    from parallel_convolution_tpu.utils.platform import device_on_tpu

    devs = jax.devices()
    m = mesh_lib.make_grid_mesh(devs[: min(4, len(devs))])
    assert step._mesh_interpret(m) is (not device_on_tpu(devs[0]))

    class FakeTpuDevice:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    class FakeMesh:
        devices = np.asarray([[FakeTpuDevice()]])

    assert step._mesh_interpret(FakeMesh()) is False
