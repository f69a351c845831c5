"""One ``run_planar`` job as one device program (``step._build_job``).

The cast to the launch's storage, the pad to its blocks, the iterations,
the crop and the cast back run as one program that donates nothing.  On
the conftest's eight CPU devices: bytes against the serial oracle on an
image the grid does not divide, one program per warm call, the caller's
array left alive, and the compiled text naming the program the call
launches.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from parallel_convolution_tpu.models import ConvolutionModel
from parallel_convolution_tpu.obs import events, metrics
from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import step
from parallel_convolution_tpu.parallel.mesh import (
    block_sharding, make_grid_mesh,
)

SHAPE, ITERS, FUSE = (3, 19, 27), 5, 2   # 19x27 divides no grid below
STORAGES = ("u8", "bf16", "f32")
GRIDS = ((2, 2), (1, 4))


@pytest.fixture(autouse=True)
def _obs_on_no_log():
    """Spans on (the program's ``pctpu.*`` spans are what these read), no
    event log."""
    was = metrics.enabled()
    metrics.set_enabled(True)
    events.deconfigure()
    yield
    metrics.set_enabled(was)


def _mesh(grid):
    return make_grid_mesh(jax.devices()[: grid[0] * grid[1]], grid)


@pytest.fixture(scope="module")
def image():
    return np.random.default_rng(11).integers(0, 256, SHAPE, np.uint8)


@pytest.fixture(scope="module")
def want(image):
    filt = filters.get_filter("blur3")
    return np.stack([oracle.run_serial_u8(p, filt, ITERS) for p in image])


def _run(entry, grid, storage, x):
    if entry == "run_planar":
        return ConvolutionModel("blur3", mesh=_mesh(grid),
                                backend="pallas_rdma", storage=storage,
                                fuse=FUSE).run_planar(x, ITERS)
    return step.sharded_iterate(x, filters.get_filter("blur3"), ITERS,
                                mesh=_mesh(grid), backend="pallas_rdma",
                                storage=storage, fuse=FUSE)


@pytest.mark.parametrize("storage", STORAGES)
@pytest.mark.parametrize("grid", GRIDS, ids=["2x2", "1x4"])
@pytest.mark.parametrize("entry", ["run_planar", "sharded_iterate"])
def test_padded_rdma_job_equals_the_oracle(image, want, entry, grid,
                                           storage):
    launch = step.resolve_launch(_mesh(grid), filters.get_filter("blur3"),
                                 SHAPE, backend="pallas_rdma",
                                 storage=storage, fuse=FUSE)
    bh, bw = launch.block_hw
    assert bh * grid[0] > SHAPE[1] and bw * grid[1] > SHAPE[2]  # padded
    out = _run(entry, grid, storage, jnp.asarray(image, jnp.float32))
    assert out.shape == SHAPE and out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out).astype(np.uint8), want)


def _launches_in_run_planar(log_dir):
    """Executables the calling thread launched inside each
    ``pctpu.run_planar`` span, and the jitted functions it called; those
    a launch runs from inside itself (interpreted kernels' callbacks) are
    part of it and not counted."""
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            launches = [ev for ev in events
                        if ev[2].endswith("Executable::Execute")
                        or ev[2].startswith("PjitFunction(")]
            outer = [ev for ev in launches if not any(
                o[0] <= ev[0] and ev[1] <= o[1] and o[2] != ev[2]
                and o[2].endswith("Executable::Execute")
                for o in launches)]
            for a, b, name in events:
                if name != "pctpu.run_planar":
                    continue
                inner = [n for s, e, n in outer if a <= s and e <= b]
                out.append((sum(n.endswith("Executable::Execute")
                                for n in inner),
                            {n[len("PjitFunction("):-1] for n in inner
                             if n.startswith("PjitFunction(")}))
    return out


@pytest.mark.parametrize("storage,filt", [
    ("u8", "blur3"), ("bf16", "edge5"), ("f32", "edge5")])
def test_warm_run_planar_launches_one_program(tmp_path, image, storage,
                                              filt):
    """One program a call, where the input contract is not checked: in u8
    storage, or with a filter that is not convex (a checked call adds
    the check's one reduction)."""
    model = ConvolutionModel(filt, mesh=_mesh((2, 2)),
                             backend="pallas_rdma", storage=storage,
                             fuse=FUSE)
    x = jnp.asarray(image, jnp.float32)
    model.run_planar(x, ITERS).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        model.run_planar(x, ITERS).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert _launches_in_run_planar(str(tmp_path)) == [(1, {"job"})]


@pytest.mark.parametrize("storage", STORAGES)
@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_the_callers_array_survives(grid, storage):
    """No storage donates the caller's float32 array: not even f32, whose
    cast is the identity and whose blocks the grid divides."""
    mesh = _mesh(grid)
    host = np.random.default_rng(5).integers(0, 256, (1, 16, 24))
    x = jax.device_put(host.astype(np.float32), block_sharding(mesh))
    out = ConvolutionModel("blur3", mesh=mesh, storage=storage).run_planar(
        x, ITERS)
    assert not x.is_deleted()
    np.testing.assert_array_equal(np.asarray(x), host)
    assert out.sharding == block_sharding(mesh)


def test_input_committed_to_another_mesh(image, want):
    """An array committed to other devices, or to the same devices in
    another order, is moved onto the job program's mesh."""
    first = ConvolutionModel("identity3", mesh=_mesh((1, 4))).run_planar(
        jnp.asarray(image, jnp.float32), 1)
    devs = jax.devices()[:4]
    other = make_grid_mesh([devs[0], devs[2], devs[1], devs[3]], (2, 2))
    out = ConvolutionModel("blur3", mesh=other, backend="pallas_rdma",
                           storage="u8", fuse=FUSE).run_planar(first, ITERS)
    np.testing.assert_array_equal(np.asarray(out).astype(np.uint8), want)


def test_iterate_text_is_the_program_run_planar_launches(tmp_path):
    """The compiled text names the module a traced ``run_planar``
    launches (``jit_`` and the jitted function's name), and its scopes
    still split the halo, the kernels' io and the job's io."""
    model = ConvolutionModel("blur3", mesh=_mesh((2, 2)),
                             backend="pallas_sep", storage="u8", fuse=FUSE)
    shape = (1, 30, 46)       # 15x23 blocks, padded for the Pallas tiles
    x = jax.device_put(np.ones(shape, np.float32),
                       block_sharding(model.mesh))
    model.run_planar(x, ITERS).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        model.run_planar(x, ITERS).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ((_, (fn,)),) = _launches_in_run_planar(str(tmp_path))
    text = model.iterate_text(shape, ITERS)
    assert text.split()[:2] == ["HloModule", f"jit_{fn},"]
    scopes = set(step.op_scopes(text).values())
    assert {"pctpu.halo", "pctpu.kernel_io", "pctpu.job_io"} <= scopes


@pytest.mark.parametrize("storage,value,raises", [
    ("f32", 255.25, True),     # out of contract as it is
    ("bf16", 255.25, False),   # bfloat16 rounds it to 255
    ("bf16", 255.75, True),    # ... and this to 256
    ("u8", 300.0, False),      # a u8 carry is not checked
])
def test_contract_reads_the_values_as_stored(storage, value, raises):
    """The check reads the caller's array as the storage will hold it,
    as the check of the cast copy did."""
    x = np.full((1, 16, 24), 7.0, np.float32)
    x[0, 3, 4] = value
    model = ConvolutionModel("blur3", mesh=_mesh((1, 1)), storage=storage)
    if raises:
        with pytest.raises(ValueError, match="outside the u8 contract"):
            model.run_planar(x, 1)
    else:
        model.run_planar(x, 1).block_until_ready()
