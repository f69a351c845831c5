"""Elastic mesh recovery (round 10): grid-shape-agnostic checkpoint
resharding, shard quarantine with named causes, supervisor reshape legs,
and serve-through-shrink in the serving engine."""

import sys
import threading

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.resilience import elastic, faults
from parallel_convolution_tpu.resilience.retry import RetryPolicy
from parallel_convolution_tpu.resilience.supervisor import Leg, Supervisor
from parallel_convolution_tpu.utils import checkpoint, imageio
from parallel_convolution_tpu.utils import platform as platform_lib


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]],
                                   shape)


def _prepare(img, m, filt):
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    return step._prepare(x, m, step.resolve_launch(m, filt, x.shape))


def _make_snapshots(ckpt_dir, img, m, filt, total=6, every=2, fuse=1):
    """run_checkpointed leaving snapshots at `every` boundaries."""
    xs, valid_hw, _ = _prepare(img, m, filt)
    checkpoint.run_checkpointed(
        xs, filt, total_iters=total, mesh=m, valid_hw=valid_hw,
        ckpt_dir=ckpt_dir, every=every, fuse=fuse)
    return valid_hw


# ------------------------------------------------ checkpoint resharding
@pytest.mark.parametrize("target", [(1, 2), (2, 2), (1, 1), (4, 2)])
def test_reshard_resume_bitexact(tmp_path, grey_odd, target):
    """The acceptance property: a snapshot written on the 2x4 mesh
    resumes byte-identically (vs the single-device oracle) on shrunken
    AND re-gridded meshes, with a fused (mid-`fuse`) iteration count —
    snapshots land at 3 and 6 with fuse=2, so the resumed run continues
    from a chunk boundary that is not a fuse multiple."""
    filt = filters.get_filter("blur3")
    total, every, fuse = 11, 3, 2
    ck = tmp_path / "ck"
    _make_snapshots(ck, grey_odd, _mesh((2, 4)), filt, total=8, every=every,
                    fuse=fuse)
    assert checkpoint.load_meta(ck)["iters_done"] == 6
    tmesh = _mesh(target)
    xs, valid_hw, _ = _prepare(grey_odd, tmesh, filt)
    with pytest.warns(checkpoint.CheckpointWarning, match="resharding"):
        out = checkpoint.run_checkpointed(
            xs, filt, total_iters=total, mesh=tmesh, valid_hw=valid_hw,
            ckpt_dir=ck, every=every, fuse=fuse)
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    want = oracle.run_serial_u8(grey_odd, filt, total)
    np.testing.assert_array_equal(got[0], want)


def test_reshard_load_state_bytes_equal(tmp_path, rgb_odd):
    """load_state onto a different grid returns the same global pixels
    as loading onto the grid that wrote it (RGB + odd dims: the pad rim
    really changes between the grids)."""
    filt = filters.get_filter("gaussian5")
    src = _mesh((2, 2))
    ck = tmp_path / "ck"
    valid_hw = _make_snapshots(ck, rgb_odd, src, filt, total=4, every=2)
    same, meta_same = checkpoint.load_state(ck, src)
    with pytest.warns(checkpoint.CheckpointWarning, match="resharding"):
        other, meta_other = checkpoint.load_state(ck, _mesh((1, 2)))
    assert "resharded_from" not in meta_same
    assert meta_other["resharded_from"] == [2, 2]
    assert meta_other["iters_done"] == meta_same["iters_done"]
    H, W = valid_hw
    np.testing.assert_array_equal(np.asarray(same)[:, :H, :W],
                                  np.asarray(other)[:, :H, :W])


# ------------------------------------------------ quarantine diagnosis
@pytest.mark.parametrize("damage,cause", [
    ("missing", "missing shard_1_0.npy"),
    ("bitflip", "checksum mismatch in shard_1_0.npy"),
    ("truncate", "truncated shard_1_0.npy"),
    ("meta", "unreadable meta"),
])
def test_quarantine_warning_names_snapshot_shard_and_cause(
        tmp_path, grey_odd, damage, cause):
    filt = filters.get_filter("blur3")
    ck = tmp_path / "ck"
    _make_snapshots(ck, grey_odd, _mesh((2, 2)), filt, total=6, every=2)
    latest = ck / (ck / "LATEST").read_text().strip()
    victim = latest / "shard_1_0.npy"
    if damage == "missing":
        victim.unlink()
    elif damage == "bitflip":
        raw = bytearray(victim.read_bytes())
        raw[-1] ^= 0xFF
        victim.write_bytes(bytes(raw))
    elif damage == "truncate":
        victim.write_bytes(victim.read_bytes()[:-8])
    else:
        (latest / "meta.json").write_text("{not json")
    with pytest.raises(checkpoint.CheckpointCorrupt) as ei:
        checkpoint.load_state(ck, _mesh((2, 2)))
    assert cause in str(ei.value) and latest.name in str(ei.value)
    # fallback quarantines ONLY that snapshot; the warning carries the
    # snapshot name and the per-shard cause for triage.
    with pytest.warns(checkpoint.CheckpointWarning) as rec:
        _, meta = checkpoint.load_state(ck, _mesh((2, 2)), fallback=True)
    text = "".join(str(w.message) for w in rec)
    assert cause in text and latest.name in text
    assert meta["iters_done"] == 2  # snapshots were at 2 and 4


def test_io_read_fault_quarantines_and_resume_is_bitexact(tmp_path,
                                                          grey_odd):
    """Acceptance: an injected io_read fault during shard validation
    quarantines only the newest snapshot (named cause) and recovery
    reshards from the next valid one, byte-identical to the oracle."""
    filt = filters.get_filter("blur3")
    total, every = 9, 2
    ck = tmp_path / "ck"
    _make_snapshots(ck, grey_odd, _mesh((2, 4)), filt, total=total,
                    every=every)
    latest = (ck / "LATEST").read_text().strip()
    tmesh = _mesh((2, 2))
    xs, valid_hw, _ = _prepare(grey_odd, tmesh, filt)
    with faults.injected("io_read:1") as plan:
        with pytest.warns(checkpoint.CheckpointWarning) as rec:
            out = checkpoint.run_checkpointed(
                xs, filt, total_iters=total, mesh=tmesh, valid_hw=valid_hw,
                ckpt_dir=ck, every=every)
        assert plan.fired
    text = "".join(str(w.message) for w in rec)
    assert latest in text and "unreadable shard_0_0.npy" in text
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    np.testing.assert_array_equal(
        got[0], oracle.run_serial_u8(grey_odd, filt, total))


# ------------------------------------- prune-vs-reader/writer races
def test_candidate_walk_survives_vanished_snapshot(tmp_path, grey_odd,
                                                   monkeypatch):
    """The prune-vs-read race: a snapshot listed by _candidate_snaps but
    pruned before its meta is read must quarantine (torn meta), not
    crash the recovery walk."""
    filt = filters.get_filter("blur3")
    ck = tmp_path / "ck"
    _make_snapshots(ck, grey_odd, _mesh((2, 2)), filt, total=6, every=2)
    real = checkpoint._candidate_snaps(ck)
    ghost = ck / "it_99999999"  # pruned between listing and meta read
    monkeypatch.setattr(checkpoint, "_candidate_snaps",
                        lambda d: [ghost] + real)
    with pytest.warns(checkpoint.CheckpointWarning, match="unreadable meta"):
        _, meta = checkpoint.load_state(ck, _mesh((2, 2)), fallback=True)
    assert meta["iters_done"] == 4


def test_concurrent_writer_prune_vs_reader(tmp_path, grey_small):
    """A writer snapshotting (and pruning) while a reader walks the
    candidate list: the prune-vs-read race round 7 only covered via the
    torn-LATEST case.  The reader may see a quarantined (vanishing)
    snapshot — a typed CheckpointCorrupt, absorbed by fallback — but
    never a raw OSError from a dir pruned mid-walk, and the final state
    must load cleanly."""
    import warnings

    filt = filters.get_filter("blur3")
    m = _mesh((1, 1))
    xs, valid_hw, _ = _prepare(grey_small, m, filt)
    ck = tmp_path / "ck"
    base = {"valid_hw": list(valid_hw), "grid": [1, 1],
            "shape": list(xs.shape)}
    errors, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            try:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    checkpoint.load_state(ck, m, fallback=True)
                checkpoint._candidate_snaps(ck)
            except FileNotFoundError:
                pass  # nothing written yet
            except checkpoint.CheckpointCorrupt:
                pass  # every candidate vanished mid-walk: typed, retryable
            except Exception as e:  # noqa: BLE001 — the hardening target
                errors.append(repr(e))
                return

    t = threading.Thread(target=reader)
    t.start()
    try:
        for it in range(1, 15):
            checkpoint.save_state(ck, xs, dict(base, iters_done=it))
    finally:
        stop.set()
        t.join(60)
    assert not errors
    arr, meta = checkpoint.load_state(ck, m, fallback=True)
    assert meta["iters_done"] == 14
    np.testing.assert_array_equal(np.asarray(arr), np.asarray(xs))
    names = [p.name for p in checkpoint._candidate_snaps(ck)]
    assert names[0] == "it_00000014" and len(names) == checkpoint.KEEP_SNAPSHOTS


# ------------------------------------------------ elastic primitives
def test_grid_ladder_and_next_fit():
    assert elastic.grid_ladder((2, 4)) == ["2x4", "2x2", "2x1", "1x1"]
    assert elastic.grid_ladder((1, 1)) == ["1x1"]
    ladder = elastic.grid_ladder((2, 4))
    assert elastic.next_fit(ladder, 1, live=2) == 2      # 2x1 fits 2
    assert elastic.next_fit(ladder, 1, live=None) == 1   # unknown: one rung
    assert elastic.next_fit(ladder, 1, live=0) == 3      # nothing fits: last
    assert elastic.next_fit(ladder, 99, live=8) == 3     # clamped


def test_probe_device_count_sim_override(monkeypatch):
    monkeypatch.setenv(platform_lib.SIM_DEVICES_ENV, "3")
    assert platform_lib.probe_device_count() == 3


def test_detect_change_proposes_fitting_spec(monkeypatch):
    m = _mesh((2, 4))
    monkeypatch.setenv(platform_lib.SIM_DEVICES_ENV, "8")
    assert elastic.detect_change(m) is None  # nothing lost
    monkeypatch.setenv(platform_lib.SIM_DEVICES_ENV, "4")
    ch = elastic.detect_change(m)
    assert ch.lost == 4 and ch.new_spec == "2x2"
    monkeypatch.setenv(platform_lib.SIM_DEVICES_ENV, "0")
    assert elastic.detect_change(m).new_spec is None


def test_reshape_mesh_builds_and_validates():
    m = elastic.reshape_mesh("1x2")
    assert mesh_lib.grid_shape(m) == (1, 2)
    with pytest.raises(ValueError, match="devices"):
        elastic.reshape_mesh((99, 99))


# ------------------------------------------------ supervisor reshape leg
def test_supervisor_reshape_leg_walks_mesh_ladder(tmp_path, monkeypatch):
    """A leg that dies with a device-loss signature on grids bigger than
    the (simulated) live-device count walks its mesh ladder — skipping
    rungs that cannot fit — and completes on the one that does."""
    monkeypatch.setenv(platform_lib.SIM_DEVICES_ENV, "2")
    done = tmp_path / "out.json"
    script = (
        "import os, sys, pathlib\n"
        "m = os.environ.get('PCTPU_MESH', '')\n"
        "if m != '1x2':\n"
        "    print('DEVICE LOST on mesh ' + m, file=sys.stderr)\n"
        "    sys.exit(1)\n"
        f"pathlib.Path({str(done)!r}).write_text('served on ' + m)\n"
    )
    leg = Leg(name="reshapey", cmd=[sys.executable, "-c", script],
              done_file=str(done), meshes=["2x4", "2x2", "1x2"],
              reshape_pattern="DEVICE LOST")
    sup = Supervisor([leg], tmp_path,
                     policy=RetryPolicy(max_attempts=3, base_delay=0.01,
                                        max_delay=0.01),
                     sleep=lambda d: None, log=lambda m: None)
    assert sup.run() == 0
    st = sup._status["legs"]["reshapey"]
    # live=2: the probe skips 2x2 (needs 4) straight to 1x2.
    assert st["mesh"] == "1x2" and st["reshapes"] == 1
    assert st["attempts"] == 2
    assert done.read_text() == "served on 1x2"


def test_leg_validation_rejects_bad_reshape_config():
    with pytest.raises(ValueError, match="meshes ladder"):
        Leg.from_dict({"name": "x", "cmd": ["true"],
                       "reshape_pattern": "boom"})
    with pytest.raises(ValueError, match="RxC"):
        Leg.from_dict({"name": "x", "cmd": ["true"],
                       "meshes": ["2x4", "nope"]})


# ------------------------------------------------ mesh swap in the stack
def test_reshard_prepared_matches_prepare(grey_odd):
    filt = filters.get_filter("blur3")
    src, dst = _mesh((2, 4)), _mesh((1, 2))
    xs, valid_hw, _ = _prepare(grey_odd, src, filt)
    moved = step.reshard_prepared(xs, valid_hw, dst)
    fresh, _, _ = _prepare(grey_odd, dst, filt)
    assert moved.shape == fresh.shape
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(fresh))


def test_model_set_mesh_bitexact(grey_small):
    from parallel_convolution_tpu.models import ConvolutionModel

    model = ConvolutionModel(filt="blur3", mesh=_mesh((2, 4)))
    a = model.run_image(grey_small, 3)
    model.set_mesh("1x2")
    assert mesh_lib.grid_shape(model.mesh) == (1, 2)
    b = model.run_image(grey_small, 3)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        b, oracle.run_serial_u8(grey_small, filters.get_filter("blur3"), 3))


# ------------------------------------------------ serve-through-shrink
def test_service_reshape_serves_through_shrink(grey_small):
    """Acceptance: the serving engine survives a mesh shrink without a
    process restart — in-flight requests drain and complete on the old
    grid, the executable cache re-warms on the new one, and every
    response stamps the grid that produced its bytes."""
    from parallel_convolution_tpu.serving.service import (
        ConvolutionService, Request, Response,
    )

    filt = filters.get_filter("blur3")
    want = oracle.run_serial_u8(grey_small, filt, 2)
    svc = ConvolutionService(_mesh((2, 4)), max_delay_s=0.05, max_batch=4)
    try:
        def req():
            return Request(image=grey_small, filter_name="blur3", iters=2)

        first = svc.submit(req())
        assert isinstance(first, Response)
        assert first.effective_grid == "2x4"
        np.testing.assert_array_equal(first.image, want)
        # In-flight at reshape time: enqueued, not yet executed — the
        # drain must complete them on the OLD grid.
        slots = [svc.submit(req(), wait=False) for _ in range(3)]
        info = svc.reshape("1x2")
        assert info["old_grid"] == (2, 4) and info["grid"] == (1, 2)
        assert info["rewarmed"] == 1 and info["skipped"] == 0
        for s in slots:
            r = s.result(60)
            assert isinstance(r, Response) and r.effective_grid == "2x4"
            np.testing.assert_array_equal(r.image, want)
        # Post-shrink requests ride the re-warmed executable: the compile
        # counter must not move.
        compiles = svc.engine.stats["compiles"]
        after = svc.submit(req())
        assert isinstance(after, Response)
        assert after.effective_grid == "1x2"
        np.testing.assert_array_equal(after.image, want)
        assert svc.engine.stats["compiles"] == compiles
        assert svc.engine.stats["reshapes"] == 1
        assert svc.stats["reshapes"] == 1
        snap = svc.snapshot()
        assert snap["mesh"] == "1x2"
        assert len(snap["resident"]) == 1  # the re-warmed key survived
    finally:
        svc.close()


def test_engine_reshape_skips_unfittable_keys_and_guards_stale(grey_small):
    from parallel_convolution_tpu.serving.engine import WarmEngine

    eng = WarmEngine(_mesh((1, 2)), fallback=False)
    # 3x40 gaussian5 (radius 2): fine on 1x2 (block rows 3 >= 2), no
    # home on 4x2 (block rows 1 < radius) — must be skipped, not fatal.
    key_small = eng.key_for((1, 3, 40), filter_name="gaussian5", iters=2)
    key_ok = eng.key_for((1, 24, 36), filter_name="blur3", iters=2)
    imgs = (imageio.interleaved_to_planar(grey_small)
            .astype(np.float32)[None])
    eng.run_batch(key_ok, imgs)
    eng.entry(key_small)
    with pytest.warns(UserWarning, match="no home"):
        info = eng.reshape(_mesh((4, 2)))
    assert info["rewarmed"] >= 1 and info["skipped"] == 1
    with pytest.raises(ValueError, match="stale"):
        eng.run_batch(key_ok, imgs)  # old-grid key after the swap
