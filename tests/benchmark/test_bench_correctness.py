"""What decides ``correct``: the plain reference, its control, and faults.

The reference agrees with the program's own serial oracle (a witness: the
harness never imports it); the control (the reference in bfloat16, put in
the program's place) and each fault planted under the timed path make a
whole run of the harness come out not correct.
"""

import numpy as np
import pytest

from benchmark import closed_loop, control, reference, run


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "ACCELERATOR", "cpu")
    monkeypatch.setattr(run, "_configure_jax", lambda: None)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))


def cell_named(name):
    """A cell of BENCHMARK.json, or, for a cell the benchmark has not
    taken yet, one made by its name ``<config>.<traffic>`` from the
    configuration and traffic files."""
    spec = run.load_spec()
    if name in {w["name"] for w in spec["workloads"]}:
        return run.cell_of(spec, name)
    config, traffic = name.split(".")
    traffic = run.load_json(f"{run.BENCH}/traffic/{traffic}.json")
    R, C = traffic["mesh"]
    return run.Cell(name=name, chips=R * C, traffic=traffic,
                    config=run.load_json(
                        f"{run.BENCH}/configs/{config}.json"),
                    end_to_end=[], per_layer=[])


def tiny(name, iters=6, fuse=2, block=(24, 40)):
    cell = cell_named(name)
    cell.config["block_hw"] = list(block)
    cell.config["iters_per_job"] = iters
    cell.config["plan"]["fuse"] = fuse
    return cell


CELLS = ["blur3_rgb_16k.iterate", "edge5_grey_2k.iterate",
         "edge5_grey_2k.mesh2x2", "blur3_rgb_16k.mesh2x2"]
CONFIGS = ["blur3_rgb_16k", "edge5_grey_2k", "rgb_2520"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_serial_oracle(name):
    from parallel_convolution_tpu.ops import oracle
    from parallel_convolution_tpu.ops.filters import get_filter

    conf = run.load_json(f"{run.BENCH}/configs/{name}.json")
    taps = reference.taps_of(conf)
    img = np.asarray(closed_loop.device_image((conf["channels"], 29, 37),
                                              11)).astype(np.uint8)
    host = reference.numpy_run(img, taps, 12)
    dev = np.asarray(reference.device_run(img, taps, 12))
    want = oracle.run_serial_u8(np.moveaxis(img, 0, -1).squeeze(-1)
                                if conf["channels"] == 1
                                else np.moveaxis(img, 0, -1),
                                get_filter(conf["filter"]), 12)
    want = want[None] if want.ndim == 2 else np.moveaxis(want, -1, 0)
    assert np.array_equal(host, want)
    assert np.array_equal(dev, want)


@pytest.mark.parametrize("name", CONFIGS)
def test_bfloat16_reference_differs(name):
    conf = run.load_json(f"{run.BENCH}/configs/{name}.json")
    taps = reference.taps_of(conf)
    # edge5 saturates most pixels to 0 or 255, where bfloat16's rounding
    # rarely flips a byte: it takes some 10^4 pixels to show reliably.
    img = closed_loop.device_image((conf["channels"], 128, 256), 5)
    f32 = reference.device_run(img, taps, 4, "float32")
    bf16 = reference.device_run(img, taps, 4, "bfloat16")
    assert reference.compare(bf16, f32)["mismatched_bytes"] > 0
    assert reference.compare(f32, f32) == {"mismatched_bytes": 0,
                                           "max_abs_diff": 0}


def test_compare_counts_every_differing_byte():
    a = np.zeros((2, 4, 4), np.uint8)
    b = a.copy()
    b[1, 2, 3] = 7
    b[0, 0, 0] = 1
    assert reference.compare(b, a) == {"mismatched_bytes": 2,
                                       "max_abs_diff": 7}
    assert reference.compare(a[:1], a)["mismatched_bytes"] == a.size


def on_2x2(shape, seed):
    """A (C, H, W) seeded image in the block sharding of a 2x2 mesh of
    the suite's host devices, and that sharding."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    sharding = NamedSharding(mesh, PartitionSpec(None, "x", "y"))
    return closed_loop.device_image(shape, seed, sharding), sharding


@pytest.mark.parametrize("name,shape", [
    ("blur3_rgb_16k", (3, 26, 38)), ("edge5_grey_2k", (1, 30, 34)),
    ("rgb_2520", (3, 24, 40))])
def test_sharded_reference_agrees_with_one_device(name, shape):
    conf = run.load_json(f"{run.BENCH}/configs/{name}.json")
    taps = reference.taps_of(conf)
    image, _ = on_2x2(shape, 17)
    one = reference.device_run(np.asarray(image), taps, 9)
    split = reference.device_run(image, taps, 9)
    assert np.array_equal(np.asarray(split), np.asarray(one))
    assert np.array_equal(np.asarray(one), reference.numpy_run(
        np.asarray(image).astype(np.uint8), taps, 9))
    assert reference.compare(split, one) == {"mismatched_bytes": 0,
                                             "max_abs_diff": 0}


@pytest.mark.parametrize("at", [(0, 11, 7), (0, 12, 30), (2, 5, 18),
                                (1, 13, 19)],
                         ids=["above_row_cut", "below_row_cut",
                              "left_of_col_cut", "corner"])
def test_one_byte_by_a_shard_boundary_counts_once(at):
    conf = run.load_json(f"{run.BENCH}/configs/blur3_rgb_16k.json")
    image, _ = on_2x2((3, 24, 38), 23)
    want = reference.device_run(image, reference.taps_of(conf), 5)
    got = want.at[at].set(want[at] ^ 1)
    assert reference.compare(got, want) == {"mismatched_bytes": 1,
                                            "max_abs_diff": 1}


def test_reference_keeps_the_mesh_sharding():
    import jax
    import jax.numpy as jnp

    conf = run.load_json(f"{run.BENCH}/configs/blur3_rgb_16k.json")
    taps = tuple(map(tuple, reference.taps_of(conf)))
    image, sharding = on_2x2((3, 24, 40), 29)
    want = reference.device_run(image, taps, 3)
    assert want.sharding.is_equivalent_to(sharding, 3)
    assert {s.data.shape for s in want.addressable_shards} == {(3, 12, 20)}
    # Neither the reference nor the comparison gathers an image: only
    # ghost bands (collective-permutes) and scalars (all-reduces) move.
    plane = reference._plane_sharding(image)
    text = reference._plane_runner(taps, 3, "float32", plane).lower(
        image, 0).compile().as_text()
    assert "collective-permute" in text and "all-gather" not in text
    u8 = jax.ShapeDtypeStruct(image.shape, jnp.uint8, sharding=sharding)
    text = reference._differ().lower(u8, u8).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text


@pytest.mark.parametrize("name", CELLS)
def test_control_comes_out_not_correct(on_cpu, name):
    # The control replaces the program, so a larger block costs little.
    cell = tiny(name, iters=4, block=(256, 256))
    res = run.run_cell(cell, 2**31 + 21, 0.2, False,
                       job=control.control_job(cell))
    assert res["correct"] is False and res["failed"] == 1
    assert res["checks"]["mismatched_bytes"]["value"] > 0


def _identity(program):
    return lambda x: x


def _half(program):
    def job(x):
        h = x.shape[1] // 2
        rest = x[:, h:, :]  # before the program, which may donate x
        out = program(x)
        return out.at[:, h:, :].set(rest.astype(out.dtype))
    return job


def _altered(program):
    return lambda x: program(x).at[0, 3, 5].add(1)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_identity, _half, _altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
def test_fault_under_the_timed_path_is_not_correct(on_cpu, monkeypatch,
                                                   name, fault):
    cell = tiny(name)
    real = run.program_job

    def broken(cell, devs):
        program, mesh = real(cell, devs)
        return fault(program), mesh

    monkeypatch.setattr(run, "program_job", broken)
    res = run.run_cell(cell, 2**31 + 33, 0.2, False)
    assert res["correct"] is False
    assert res["checks"]["mismatched_bytes"]["value"] > 0


def _exchange_left_out(monkeypatch, name, seed):
    """A run of a 2x2 cell with every ppermute's payload replaced by
    zeros."""
    from parallel_convolution_tpu.parallel import halo, step

    cell = tiny(name)
    monkeypatch.setattr(halo, "_shift",
                        lambda x, *a, **k: np.zeros(x.shape, x.dtype)
                        if not hasattr(x, "at") else x * 0)
    step._build_iterate.cache_clear()
    try:
        return run.run_cell(cell, seed, 0.2, False)
    finally:
        step._build_iterate.cache_clear()


def test_exchange_left_out_is_not_correct(on_cpu, monkeypatch):
    """The 2x2 mesh with every ppermute's payload replaced by zeros."""
    res = _exchange_left_out(monkeypatch, "edge5_grey_2k.mesh2x2",
                             2**31 + 44)
    assert res["correct"] is False
    assert res["checks"]["mismatched_bytes"]["value"] > 0


def test_exchange_left_out_of_the_flagship_mesh_is_not_correct(
        on_cpu, monkeypatch):
    """The same fault in the north star's 2x2 piece (no cell yet), where
    the separable u8 kernel takes the ghost bands."""
    res = _exchange_left_out(monkeypatch, "blur3_rgb_16k.mesh2x2",
                             2**31 + 45)
    assert res["correct"] is False
    assert res["checks"]["mismatched_bytes"]["value"] > 0
