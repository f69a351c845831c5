"""CPU rehearsal of the harness's data path.

Every configuration, cell and metric is found by name from BENCHMARK.json;
one cell runs end to end at a tiny size with the chip check steered here,
in the test; a run off the TPU exits non-zero and prints no result.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return run.load_spec()


def _one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_spec_has_exactly_the_contract_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert spec["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json")) < 65536


def test_spec_entries_keep_to_the_contract(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert _one_line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmark/")
    cells = spec["workloads"]
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    reports = {w["name"]: {e["name"] for e in spec["end_to_end"]
                           if run.applies(e, w["name"])} for w in cells}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _one_line(m["layer"])
        for cell in m.get("workloads", reports):  # each reports what it moves
            assert m["moves"] in reports[cell], (m["name"], cell)
    for cell, names in reports.items():
        assert "setup_s" in names and len(names) >= 2, cell
        assert any(run.applies(m, cell) for m in spec["per_layer"]), cell
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"} and m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in cells}
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


def test_every_file_under_paths_is_named_from_name_characters(spec):
    for top in spec["paths"]:
        for d, _, files in os.walk(os.path.join(run.ROOT, top)):
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), run.ROOT)
                if "__pycache__" in rel:
                    continue
                assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


@pytest.mark.parametrize("name,rate", [
    ("blur3_rgb_16k.iterate", "gpx_per_s_per_chip"),
    ("edge5_grey_2k.iterate", "gpx_per_s_per_chip.short_jobs"),
    ("edge5_grey_2k.mesh2x2", "gpx_per_s_per_chip")])
def test_every_cell_loads_by_name(spec, name, rate):
    cell = run.cell_of(spec, name)
    assert cell.chips == cell.traffic["mesh"][0] * cell.traffic["mesh"][1]
    assert cell.config["name"] == name.split(".")[0]
    assert [m["name"] for m in cell.end_to_end] == [rate, "setup_s"]
    per_layer = {m["name"] for m in cell.per_layer}
    assert {m["moves"] for m in cell.per_layer} == {rate}
    assert ("exchange_exposed_ms" in per_layer) == (cell.chips == 4)
    group = rate[len("gpx_per_s_per_chip"):]
    assert {f"host_call_ms{group}", f"kernel_ms{group}",
            f"stencil_roofline{group}"} <= per_layer


def test_every_metric_has_a_reader(spec):
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.metric_reader(m["name"]).read), m["name"]


def test_configs_match_the_programs_registry(spec):
    import numpy as np

    from benchmark import reference
    from parallel_convolution_tpu.ops.filters import get_filter

    for c in spec["configs"]:
        conf = run.load_json(os.path.join(run.ROOT, c["file"]))
        assert conf["name"] == c["name"]
        assert set(conf["reduced"]) == set(c["reduced"])
        want = get_filter(conf["filter"]).taps
        assert np.array_equal(np.asarray(reference.taps_of(conf),
                                         np.float32), want)


def tiny(cell, iters=6, fuse=2):
    cell.config["block_hw"] = [24, 40]
    cell.config["iters_per_job"] = iters
    cell.config["plan"]["fuse"] = fuse
    return cell


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """Steer the harness's chip check, cache and trace to this CPU test."""
    monkeypatch.setattr(run, "ACCELERATOR", "cpu")
    monkeypatch.setattr(run, "_configure_jax", lambda: None)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))


@pytest.mark.parametrize("trace", [False, True])
def test_one_cell_end_to_end_at_a_tiny_size(spec, on_cpu, capsys, trace):
    cell = tiny(run.cell_of(spec, "edge5_grey_2k.iterate"))
    res = run.run_cell(cell, 2**31 + 7, 0.3, trace)
    run.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    keys = list(line)
    want = {"correct", "attempted", "failed", "metrics", "device",
            "checked_job", "job_s", "checks"} | ({"breakdown"} if trace
                                                  else set())
    assert set(keys) == want and keys[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["checks"] == {"mismatched_bytes": {"value": 0, "limit": 0},
                              "max_abs_diff": {"value": 0, "limit": 0}}
    assert err.strip().splitlines()[-2:] == [
        "check mismatched_bytes 0 limit 0", "check max_abs_diff 0 limit 0"]
    if trace:
        # No device planes on the CPU: device metrics are left out, never 0.
        assert "kernel_ms.short_jobs" not in line["metrics"]
        assert "stencil_roofline.short_jobs" not in line["metrics"]
        assert line["metrics"]["host_call_ms.short_jobs"]["value"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"gpx_per_s_per_chip.short_jobs",
                                        "setup_s"}
        assert line["metrics"]["gpx_per_s_per_chip.short_jobs"]["value"] > 0


def test_same_seed_same_inputs(on_cpu):
    import numpy as np

    from benchmark import closed_loop

    a = closed_loop.device_image((1, 8, 16), 2**31 + 99)
    b = closed_loop.device_image((1, 8, 16), 2**31 + 99)
    c = closed_loop.device_image((1, 8, 16), 2**31 + 100)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(a, np.rint(a)) and 0 <= a.min() <= a.max() <= 255
    assert closed_loop.check_fraction(5) == closed_loop.check_fraction(5) \
        < closed_loop.CHECK_BEFORE


def test_window_runs_the_job_alone_on_one_resident_image(on_cpu):
    import jax
    import jax.numpy as jnp

    from benchmark import closed_loop

    image = closed_loop.device_image((1, 8, 16), 2**31 + 5)
    seen = []

    def job(x):
        seen.append(x)
        return (x + 1).astype(jnp.uint8)

    win = closed_loop.run(job, image, 0.05, 0.0)
    assert win.jobs == len(seen) >= 1 and win.kept_index == 0
    assert all(x is image for x in seen) and not image.is_deleted()
    assert jnp.array_equal(win.kept, (image + 1).astype(jnp.uint8))
    consume = jax.jit(lambda x: x * 2, donate_argnums=0)
    with pytest.raises(ValueError, match="consumed the resident image"):
        closed_loop.warm_up(consume, closed_loop.device_image((1, 8, 16), 1))


def test_traffic_key_the_loop_does_not_read_is_refused(spec, tmp_path,
                                                        monkeypatch):
    traffic = tmp_path / "traffic"
    traffic.mkdir()
    (traffic / "iterate.json").write_text(json.dumps(
        {"mesh": [1, 1], "clients": 4}))
    monkeypatch.setattr(run, "BENCH", str(tmp_path))
    with pytest.raises(ValueError, match="clients"):
        run.cell_of(spec, "edge5_grey_2k.iterate")


def test_off_tpu_run_exits_non_zero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "edge5_grey_2k.iterate", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=run.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=240)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not 'tpu'" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path, spec):
    """Only BENCHMARK.json and the paths, with the chip check steered off:
    the program is missing, so the run fails before any result."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    for top in spec["paths"]:
        shutil.copytree(os.path.join(run.ROOT, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    steer = ("import sys; import benchmark.run as r; r.ACCELERATOR = 'cpu'; "
             "r._configure_jax = lambda: None; sys.exit(r.main(["
             "'--workload', 'edge5_grey_2k.iterate', '--seed', '3', "
             "'--seconds', '1', '--trace', '0']))")
    p = subprocess.run([sys.executable, "-c", steer], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""),
                       capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "parallel_convolution_tpu" in p.stderr
