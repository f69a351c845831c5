"""End-to-end CLI + models tests — the reference's own validation workflow:
serial output vs distributed output must be byte-identical."""

import numpy as np
import pytest

from parallel_convolution_tpu import cli
from parallel_convolution_tpu.models import ConvolutionModel, JacobiSolver
from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.utils import imageio


def test_model_run_image_matches_oracle(rgb_odd):
    m = ConvolutionModel(filt="blur3", backend="shifted")
    got = m.run_image(rgb_odd, 4)
    want = oracle.run_serial_u8(rgb_odd, filters.get_filter("blur3"), 4)
    np.testing.assert_array_equal(got, want)


def test_jacobi_solver(grey_small):
    s = JacobiSolver(tol=0.5, max_iters=200, check_every=5)
    out, iters = s.solve(
        imageio.interleaved_to_planar(grey_small).astype(np.float32)
    )
    assert 0 < iters <= 200
    assert out.shape == (1, *grey_small.shape)


def test_cli_end_to_end_run_vs_serial(tmp_path, capsys):
    # generate → serial → run → compare: the full reference workflow.
    src = str(tmp_path / "in.raw")
    golden = str(tmp_path / "serial.raw")
    out = str(tmp_path / "tpu.raw")

    assert cli.main(["generate", src, "31", "45", "rgb", "--seed", "5"]) == 0
    assert cli.main(["serial", src, "31", "45", "10", "rgb",
                     "-o", golden, "--filter", "blur3"]) == 0
    assert cli.main(["run", src, "31", "45", "10", "rgb", "-o", out,
                     "--filter", "blur3", "--mesh", "2x4"]) == 0
    assert cli.main(["compare", golden, out]) == 0
    assert "identical" in capsys.readouterr().out


def test_cli_compare_differs(tmp_path, capsys):
    a, b = str(tmp_path / "a.raw"), str(tmp_path / "b.raw")
    imageio.write_raw(a, np.zeros((4, 4), np.uint8))
    imageio.write_raw(b, np.ones((4, 4), np.uint8))
    assert cli.main(["compare", a, b]) == 1
    assert "differ: 16 bytes" in capsys.readouterr().out


def test_cli_converge(tmp_path, capsys):
    src = str(tmp_path / "in.raw")
    out = str(tmp_path / "out.raw")
    cli.main(["generate", src, "24", "32", "grey"])
    assert cli.main(["run", src, "24", "32", "500", "grey", "-o", out,
                     "--filter", "blur3", "--converge", "0.5",
                     "--check-every", "5", "--mesh", "2x2"]) == 0
    assert "converged after" in capsys.readouterr().out


def test_cli_tile_flag(tmp_path, capsys):
    """--tile TH,TW reaches the Pallas kernels; output stays golden."""
    src = str(tmp_path / "in.raw")
    a, b = str(tmp_path / "a.raw"), str(tmp_path / "b.raw")
    cli.main(["generate", src, "26", "38", "grey", "--seed", "9"])
    assert cli.main(["serial", src, "26", "38", "6", "grey", "-o", a]) == 0
    assert cli.main(["run", src, "26", "38", "6", "grey", "-o", b,
                     "--mesh", "2x2", "--backend", "pallas_sep",
                     "--fuse", "3", "--tile", "16,128"]) == 0
    assert cli.main(["compare", a, b]) == 0
    with pytest.raises(SystemExit):
        cli.main(["run", src, "26", "38", "6", "grey", "-o", b,
                  "--tile", "16x128"])


def test_cli_bench_subcommand(capsys):
    """`pconv-tpu bench` prints one machine-readable row (C10 via CLI)."""
    import json

    assert cli.main(["bench", "64", "96", "3", "grey", "--mesh", "2x2",
                     "--backend", "pallas_sep", "--fuse", "2",
                     "--tile", "16,128", "--reps", "1"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["mesh"] == "2x2" and row["gpixels_per_s"] > 0
    assert row["backend"] == "pallas_sep" and row["fuse"] == 2


def test_cli_info(capsys):
    assert cli.main(["info"]) == 0
    out = capsys.readouterr().out
    assert "devices: 8" in out and "blur3" in out


def test_cli_sharded_io_and_checkpoint(tmp_path, capsys):
    src = str(tmp_path / "in.raw")
    a, b, c = (str(tmp_path / n) for n in ("a.raw", "b.raw", "c.raw"))
    cli.main(["generate", src, "30", "44", "grey", "--seed", "6"])
    assert cli.main(["serial", src, "30", "44", "8", "grey", "-o", a]) == 0
    assert cli.main(["run", src, "30", "44", "8", "grey", "-o", b,
                     "--mesh", "2x2", "--sharded-io"]) == 0
    assert cli.main(["compare", a, b]) == 0
    assert cli.main(["run", src, "30", "44", "8", "grey", "-o", c,
                     "--mesh", "2x2", "--checkpoint",
                     str(tmp_path / "ck"), "--checkpoint-every", "3"]) == 0
    assert cli.main(["compare", a, c]) == 0


def test_multihost_single_process_noops():
    from parallel_convolution_tpu.parallel import multihost

    multihost.initialize(num_processes=1)
    info = multihost.process_info()
    assert info["process_count"] == 1 and info["global_devices"] == 8
    multihost.barrier()
    assert multihost.broadcast_scalar(3.5) == 3.5


def test_medium_golden_canonical_aspect():
    # Scaled-down canonical geometry (1920x2520 -> 192x252) with the
    # reference's standard workload shape: blur3, grey, many iterations.
    from parallel_convolution_tpu.parallel import mesh as mesh_lib
    import jax

    img = imageio.generate_test_image(192, 252, "grey", seed=77)
    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(img, filt, 25)
    m = mesh_lib.make_grid_mesh(jax.devices()[:8], (2, 4))
    model = ConvolutionModel(filt=filt, mesh=m, backend="separable",
                             storage="bf16", fuse=5)
    got = model.run_image(img, 25)
    np.testing.assert_array_equal(got, want)


def test_fast_preset_resolution(monkeypatch):
    # --fast fills only UNSET knobs (argparse None-sentinel: an explicit
    # `--fuse 1` stays unfused), only on a TPU (off-TPU the interpreter
    # would make the "fast" preset the slow one), and clamps fuse to the
    # per-device block so small images never trip the fuse>=block error.
    import argparse

    import jax

    from parallel_convolution_tpu import cli
    from parallel_convolution_tpu.parallel import mesh as mesh_lib
    from parallel_convolution_tpu.utils import platform as plat

    m = mesh_lib.make_grid_mesh(jax.devices()[:4], (2, 2))

    def ns(**kw):
        base = dict(fast=True, backend=None, storage=None, fuse=None,
                    rows=1920, cols=2520, filter_name="blur3")
        base.update(kw)
        return argparse.Namespace(**base)

    monkeypatch.setattr(plat, "on_tpu", lambda: True)
    a = ns()
    cli._resolve_perf_knobs(a, m)
    assert (a.backend, a.storage, a.fuse) == ("pallas_sep", "bf16", 32)

    a = ns(backend="pallas", fuse=1)  # explicit flags always win
    cli._resolve_perf_knobs(a, m)
    assert (a.backend, a.storage, a.fuse) == ("pallas", "bf16", 1)

    a = ns(rows=40, cols=40)  # 20x20 blocks: fuse clamps to the block
    cli._resolve_perf_knobs(a, m)
    assert a.fuse == 20

    a = ns(fast=False)
    cli._resolve_perf_knobs(a, m)
    assert (a.backend, a.storage, a.fuse) == ("shifted", "f32", 1)

    monkeypatch.setattr(plat, "on_tpu", lambda: False)
    a = ns()
    cli._resolve_perf_knobs(a, m)  # off-TPU: normal defaults
    assert (a.backend, a.storage, a.fuse) == ("shifted", "f32", 1)
