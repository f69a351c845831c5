import json

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.utils import checkpoint, imageio, tracing


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]], shape)


def _prepare(img, m, filt):
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    return step._prepare(x, m, step.resolve_launch(m, filt, x.shape))


def test_checkpointed_run_bitexact(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 4))
    xs, valid_hw, _ = _prepare(grey_odd, m, filt)
    out = checkpoint.run_checkpointed(
        xs, filt, total_iters=10, mesh=m, valid_hw=valid_hw,
        ckpt_dir=tmp_path / "ck", every=3,
    )
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    want = oracle.run_serial_u8(grey_odd, filt, 10)
    np.testing.assert_array_equal(got[0], want)
    # intermediate snapshots were written (at 3, 6, 9 but not 10)
    meta = checkpoint.load_meta(tmp_path / "ck")
    assert meta["iters_done"] == 9


def test_checkpoint_resume_continues(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    xs, valid_hw, _ = _prepare(grey_odd, m, filt)
    ck = tmp_path / "ck"
    # Simulate a killed run: snapshot at iteration 4 by hand.
    mid = step.iterate_prepared(xs, filt, 4, m, valid_hw)
    checkpoint.save_state(ck, mid, {
        "filter": filt.name, "quantize": True, "backend": "shifted",
        "fuse": 1, "boundary": "zero",
        "valid_hw": list(valid_hw), "grid": [2, 2],
        "iters_done": 4, "shape": list(mid.shape),
    })
    # Resume with xs=None: must pick up at 4 and finish 10 total.
    out = checkpoint.run_checkpointed(
        None, filt, total_iters=10, mesh=m, valid_hw=valid_hw,
        ckpt_dir=ck, every=4,
    )
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    want = oracle.run_serial_u8(grey_odd, filt, 10)
    np.testing.assert_array_equal(got[0], want)


def test_checkpoint_config_mismatch_raises(tmp_path, grey_small):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    xs, valid_hw, _ = _prepare(grey_small, m, filt)
    ck = tmp_path / "ck"
    checkpoint.save_state(ck, xs, {
        "filter": "edge3", "quantize": True, "backend": "shifted",
        "valid_hw": list(valid_hw), "grid": [2, 2],
        "iters_done": 2, "shape": list(xs.shape),
    })
    with pytest.raises(ValueError, match="config mismatch"):
        checkpoint.run_checkpointed(None, filt, 10, m, valid_hw, ck, 2)


def test_checkpoint_grid_mismatch_reshards(tmp_path, grey_small):
    # Round 10 (elastic recovery): a grid mismatch is no longer an error —
    # the snapshot reshards onto the requested mesh, bytes unchanged.
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    xs, valid_hw, _ = _prepare(grey_small, m, filt)
    checkpoint.save_state(tmp_path, xs, {
        "grid": [2, 2], "shape": list(xs.shape), "iters_done": 0,
        "valid_hw": list(valid_hw),
    })
    with pytest.warns(checkpoint.CheckpointWarning, match="resharding"):
        arr, meta = checkpoint.load_state(tmp_path, _mesh((1, 4)))
    assert meta["resharded_from"] == [2, 2] and meta["grid"] == [1, 4]
    np.testing.assert_array_equal(
        np.asarray(arr)[:, : valid_hw[0], : valid_hw[1]],
        np.asarray(xs)[:, : valid_hw[0], : valid_hw[1]])


def test_phase_timer(tmp_path):
    t = tracing.PhaseTimer()
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b", fence=jax.numpy.ones((4,))):
        pass
    rep = t.report()
    assert rep["phases"]["a"]["calls"] == 2
    assert set(rep["phases"]) == {"a", "b"}
    t.dump(tmp_path / "t.json")
    assert json.loads((tmp_path / "t.json").read_text())["total_s"] >= 0


def test_checkpoint_snapshots_pruned_and_crash_safe(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    xs, valid_hw, _ = _prepare(grey_odd, m, filt)
    ck = tmp_path / "ck"
    checkpoint.run_checkpointed(xs, filt, total_iters=10, mesh=m,
                                valid_hw=valid_hw, ckpt_dir=ck, every=2)
    snaps = sorted(p.name for p in ck.iterdir()
                   if p.is_dir() and p.name.startswith("it_"))
    # snapshots at 2,4,6,8 -> pruned to the last KEEP_SNAPSHOTS
    assert snaps == ["it_00000006", "it_00000008"]
    assert (ck / "LATEST").read_text().strip() == "it_00000008"
    # a torn newer snapshot (no meta yet) must not be picked up
    torn = ck / "it_00000010"
    torn.mkdir()
    (torn / "shard_0_0.npy").write_bytes(b"garbage")
    meta = checkpoint.load_meta(ck)
    assert meta["iters_done"] == 8
