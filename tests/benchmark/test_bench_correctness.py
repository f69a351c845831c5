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


def tiny(name, iters=6, fuse=2, block=(24, 40)):
    cell = run.cell_of(run.load_spec(), name)
    cell.config["block_hw"] = list(block)
    cell.config["iters_per_job"] = iters
    cell.config["plan"]["fuse"] = fuse
    return cell


CELLS = ["blur3_rgb_16k.iterate", "edge5_grey_2k.iterate",
         "edge5_grey_2k.mesh2x2"]


@pytest.mark.parametrize("name", ["blur3_rgb_16k", "edge5_grey_2k"])
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


@pytest.mark.parametrize("name", ["blur3_rgb_16k", "edge5_grey_2k"])
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


def test_exchange_left_out_is_not_correct(on_cpu, monkeypatch):
    """The 2x2 mesh with every ppermute's payload replaced by zeros."""
    from parallel_convolution_tpu.parallel import halo, step

    cell = tiny("edge5_grey_2k.mesh2x2")
    monkeypatch.setattr(halo, "_shift",
                        lambda x, *a, **k: np.zeros(x.shape, x.dtype)
                        if not hasattr(x, "at") else x * 0)
    step._build_iterate.cache_clear()
    try:
        res = run.run_cell(cell, 2**31 + 44, 0.2, False)
    finally:
        step._build_iterate.cache_clear()
    assert res["correct"] is False
    assert res["checks"]["mismatched_bytes"]["value"] > 0
