"""Resilience subsystem: fault injection, classified retry, degradation,
hardened checkpoints, supervised legs (tests run on the 8-virtual-device
CPU mesh — no TPU needed).

The two acceptance properties from the resilience PR:

* under an injected mid-run kill at EVERY checkpoint fault site, the
  resumed output is byte-identical to an uninterrupted run
  (test_kill_at_each_checkpoint_site_resume_bitexact);
* under an injected ``backend_compile`` fault, ``fallback=True``
  completes byte-identically on the next backend in the chain and the
  emitted bench row records the effective backend
  (test_backend_compile_fault_degrades_bitexact,
  test_bench_fallback_row_records_degradation).
"""

from __future__ import annotations

import json
import subprocess
import sys
import zlib
from pathlib import Path

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.ops import filters, oracle
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.resilience import degrade, faults, retry
from parallel_convolution_tpu.resilience.supervisor import (
    Leg, Supervisor, legs_from_json,
)
from parallel_convolution_tpu.utils import bench, checkpoint, imageio


def _mesh(shape):
    return mesh_lib.make_grid_mesh(jax.devices()[: shape[0] * shape[1]], shape)


def _prepare(img, m, filt):
    x = imageio.interleaved_to_planar(img).astype(np.float32)
    return step._prepare(x, m, step.resolve_launch(m, filt, x.shape))


@pytest.fixture(autouse=True)
def _clean_global_state():
    yield
    faults.uninstall_plan()
    degrade.clear_probe_cache()


# ---------------------------------------------------------------- faults


def test_fault_point_is_noop_without_plan():
    for site in sorted(faults.KNOWN_SITES):
        faults.fault_point(site)  # must not raise, count, or allocate


def test_plan_hit_indexed_trigger():
    with faults.injected("checkpoint_write_shard:2") as plan:
        faults.fault_point("checkpoint_write_shard")  # hit 1: no fire
        with pytest.raises(faults.InjectedFault) as ei:
            faults.fault_point("checkpoint_write_shard")
        assert ei.value.site == "checkpoint_write_shard"
        assert ei.value.hit == 2
        assert ei.value.transient
        faults.fault_point("checkpoint_write_shard")  # hit 3: no fire
        assert plan.fired == [("checkpoint_write_shard", 2)]
        # sites not in the plan are free
        faults.fault_point("io_read")
        assert plan.hits("io_read") == 0


def test_plan_range_every_and_terminal_triggers():
    with faults.injected("io_read:2+,device_probe:*,backend_compile:1!"):
        faults.fault_point("io_read")
        for _ in range(3):
            with pytest.raises(faults.InjectedFault):
                faults.fault_point("io_read")
        with pytest.raises(faults.InjectedFault):
            faults.fault_point("device_probe")
        with pytest.raises(faults.InjectedFault) as ei:
            faults.fault_point("backend_compile")
        assert not ei.value.transient
        assert retry.classify(ei.value) == retry.TERMINAL


def test_plan_probability_deterministic_per_seed():
    def fires(seed):
        plan = faults.plan_from_spec("io_read:p0.5", seed=seed)
        out = []
        for _ in range(50):
            try:
                plan.check("io_read")
                out.append(False)
            except faults.InjectedFault:
                out.append(True)
        return out

    assert fires(7) == fires(7)
    assert any(fires(7)) and not all(fires(7))


def test_bad_specs_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.plan_from_spec("not_a_site:1")
    with pytest.raises(ValueError, match="bad fault spec"):
        faults.plan_from_spec("io_read")
    with pytest.raises(ValueError, match="bad fault spec"):
        faults.plan_from_spec("io_read:0")
    with pytest.raises(ValueError, match="empty"):
        faults.plan_from_spec("  ,  ")


def test_plan_from_env():
    assert faults.plan_from_env({}) is None
    plan = faults.plan_from_env(
        {"PCTPU_FAULTS": "io_read:1", "PCTPU_FAULT_SEED": "3"})
    assert plan.seed == 3 and "io_read" in plan.rules


# ----------------------------------------------------------------- retry


def test_classify_transient_terminal():
    T, X = retry.TRANSIENT, retry.TERMINAL
    assert retry.classify(RuntimeError("UNAVAILABLE: Socket closed")) == T
    assert retry.classify(RuntimeError("DEADLINE_EXCEEDED over RPC")) == T
    assert retry.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: probe OOM")) == T
    assert retry.classify(RuntimeError(
        "INTERNAL: Mosaic failed to compile")) == T
    # regression: a Mosaic crash whose text mentions vector *shapes* is
    # still the transient compile-crash class, not a contract error
    assert retry.classify(RuntimeError(
        "INTERNAL: Mosaic ... unsupported vector.shape_cast")) == T
    assert retry.classify(TimeoutError()) == T
    assert retry.classify(ConnectionError("reset")) == T
    # terminal: retrying burns chip time forever
    assert retry.classify(ValueError("checkpoint grid [2,2] != [1,4]")) == X
    assert retry.classify(ValueError("checkpoint config mismatch")) == X
    assert retry.classify(RuntimeError("magic_round_guard MISMATCH")) == X
    assert retry.classify(TypeError("bad shape")) == X
    assert retry.classify(RuntimeError("some unclassified novelty")) == X
    assert retry.classify(faults.InjectedFault("io_read", 1)) == T
    assert retry.classify(
        faults.InjectedFault("io_read", 1, transient=False)) == X


def test_with_retry_recovers_and_schedules_deterministically():
    calls, slept = [], []
    policy = retry.RetryPolicy(max_attempts=4, base_delay=1.0,
                               max_delay=60.0, seed=5)

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: backend down")
        return "ok"

    assert retry.with_retry(flaky, policy, sleep=slept.append) == "ok"
    assert len(calls) == 3
    assert slept == policy.delays()[:2]
    # deterministic: same policy/failure pattern -> same schedule
    assert policy.delays() == retry.RetryPolicy(
        max_attempts=4, base_delay=1.0, max_delay=60.0, seed=5).delays()
    # capped exponential shape: nondecreasing raw backoff, jitter in
    # [0.5, 1.0] of the raw value
    for k, d in enumerate(policy.delays(), start=1):
        raw = min(60.0, 1.0 * 2.0 ** (k - 1))
        assert 0.5 * raw <= d <= raw


def test_with_retry_terminal_raises_immediately():
    slept = []
    with pytest.raises(ValueError):
        retry.with_retry(
            lambda: (_ for _ in ()).throw(ValueError("shape wrong")),
            retry.RetryPolicy(max_attempts=5), sleep=slept.append)
    assert slept == []


def test_with_retry_exhaustion():
    slept = []
    with pytest.raises(retry.RetryExhausted):
        retry.with_retry(
            lambda: (_ for _ in ()).throw(TimeoutError("probe")),
            retry.RetryPolicy(max_attempts=3, base_delay=0.1),
            sleep=slept.append)
    assert len(slept) == 2  # no sleep after the final attempt


# ---------------------------------------------- hardened checkpoints


def _make_snapshots(tmp_path, img, m, filt, total=6, every=2):
    """run_checkpointed leaving snapshots at `every` boundaries."""
    xs, valid_hw, _ = _prepare(img, m, filt)
    out = checkpoint.run_checkpointed(
        xs, filt, total_iters=total, mesh=m, valid_hw=valid_hw,
        ckpt_dir=tmp_path / "ck", every=every)
    return tmp_path / "ck", valid_hw, out


def test_meta_records_shard_crcs(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    ck, _, _ = _make_snapshots(tmp_path, grey_odd, m, filt)
    meta = checkpoint.load_meta(ck)
    shards = meta["shards"]
    assert sorted(shards) == sorted(
        f"shard_{r}_{c}.npy" for r in range(2) for c in range(2))
    snap = ck / f"it_{meta['iters_done']:08d}"
    for name, rec in shards.items():
        raw = (snap / name).read_bytes()
        assert len(raw) == rec["bytes"]
        assert zlib.crc32(raw) == rec["crc32"]


@pytest.mark.parametrize("damage", ["missing", "truncated", "bitflip"])
def test_load_state_detects_torn_snapshot(tmp_path, grey_odd, damage):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    ck, _, _ = _make_snapshots(tmp_path, grey_odd, m, filt)
    latest = ck / (ck / "LATEST").read_text().strip()
    victim = latest / "shard_1_0.npy"
    if damage == "missing":
        victim.unlink()  # the multi-host prune race: meta without shards
    elif damage == "truncated":
        victim.write_bytes(victim.read_bytes()[:-8])
    else:
        raw = bytearray(victim.read_bytes())
        raw[-1] ^= 0xFF
        victim.write_bytes(bytes(raw))
    with pytest.raises(checkpoint.CheckpointCorrupt, match="shard_1_0"):
        checkpoint.load_state(ck, m)
    # fallback walks to the older snapshot, which still validates
    with pytest.warns(checkpoint.CheckpointWarning, match="torn"):
        _, meta = checkpoint.load_state(ck, m, fallback=True)
    assert meta["iters_done"] == 2  # snapshots were at 2 and 4


def test_run_checkpointed_resumes_through_torn_latest(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    want = oracle.run_serial_u8(grey_odd, filt, 9)
    ck, valid_hw, _ = _make_snapshots(tmp_path, grey_odd, m, filt,
                                      total=6, every=2)
    latest = ck / (ck / "LATEST").read_text().strip()
    (latest / "shard_0_1.npy").unlink()
    with pytest.warns(checkpoint.CheckpointWarning):
        out = checkpoint.run_checkpointed(
            None, filt, total_iters=9, mesh=m, valid_hw=valid_hw,
            ckpt_dir=ck, every=2)
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    np.testing.assert_array_equal(got[0], want)


def test_run_checkpointed_fresh_when_every_snapshot_torn(tmp_path, grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    want = oracle.run_serial_u8(grey_odd, filt, 6)
    ck, valid_hw, _ = _make_snapshots(tmp_path, grey_odd, m, filt)
    for snap in ck.glob("it_*"):
        (snap / "shard_0_0.npy").unlink()
    xs, valid_hw, _ = _prepare(grey_odd, m, filt)
    with pytest.warns(checkpoint.CheckpointWarning, match="starting fresh"):
        out = checkpoint.run_checkpointed(
            xs, filt, total_iters=6, mesh=m, valid_hw=valid_hw,
            ckpt_dir=ck, every=2)
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    np.testing.assert_array_equal(got[0], want)


# Acceptance: a kill at EVERY checkpoint fault site, then resume ->
# byte-identical to an uninterrupted run.  Geometry: (2,2) mesh -> 4
# shard writes per save; every=3, total=8 -> saves at 3 and 6.
# checkpoint_write_shard hits 1/3 tear the first save, hit 5 the second;
# checkpoint_write_meta hits 1/2 are the first save's meta write and
# LATEST flip, hits 3/4 the second save's.
@pytest.mark.parametrize("spec", [
    "checkpoint_write_shard:1",
    "checkpoint_write_shard:3",
    "checkpoint_write_shard:5",
    "checkpoint_write_meta:1",
    "checkpoint_write_meta:2",
    "checkpoint_write_meta:3",
    "checkpoint_write_meta:4",
])
def test_kill_at_each_checkpoint_site_resume_bitexact(tmp_path, grey_odd,
                                                      spec):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    total, every = 8, 3
    want = oracle.run_serial_u8(grey_odd, filt, total)
    ck = tmp_path / "ck"
    with faults.injected(spec) as plan:
        xs, valid_hw, _ = _prepare(grey_odd, m, filt)
        with pytest.raises(faults.InjectedFault):
            checkpoint.run_checkpointed(
                xs, filt, total_iters=total, mesh=m, valid_hw=valid_hw,
                ckpt_dir=ck, every=every)
        assert plan.fired  # the kill really happened where we asked
    # the restarted process: fresh input, no plan, same ckpt dir
    xs2, valid_hw, _ = _prepare(grey_odd, m, filt)
    out = checkpoint.run_checkpointed(
        xs2, filt, total_iters=total, mesh=m, valid_hw=valid_hw,
        ckpt_dir=ck, every=every)
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    np.testing.assert_array_equal(got[0], want)


# ------------------------------------------------- backend degradation


def test_degradation_chains():
    assert degrade.degradation_chain("pallas_rdma") == (
        "pallas_rdma", "pallas", "shifted")
    assert degrade.degradation_chain("pallas_sep") == (
        "pallas_sep", "pallas", "shifted")
    assert degrade.degradation_chain("shifted") == ("shifted",)


def test_backend_compile_fault_degrades_bitexact(grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    want = oracle.run_serial_u8(grey_odd, filt, 3)
    with faults.injected("backend_compile:1"):
        xs, valid_hw, _ = _prepare(grey_odd, m, filt)
        with pytest.warns(degrade.BackendDegradedWarning,
                          match="'pallas' degraded to 'shifted'"):
            out = step.iterate_prepared(
                xs, filt, 3, m, valid_hw, backend="pallas", fallback=True)
    got = np.asarray(out)[:, : valid_hw[0], : valid_hw[1]].astype(np.uint8)
    np.testing.assert_array_equal(got[0], want)


def test_terminal_probe_failure_does_not_degrade():
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    with faults.injected("backend_compile:1!"):  # terminal compile fault
        with pytest.raises(faults.InjectedFault):
            degrade.resolve_backend(m, filt, "pallas")


def test_probe_cached_once_per_process():
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    assert degrade.resolve_backend(m, filt, "shifted") == "shifted"
    # a plan installed AFTER the successful probe must not re-fire: the
    # (backend, config) verdict is cached per process
    with faults.injected("backend_compile:*"):
        assert degrade.resolve_backend(m, filt, "shifted") == "shifted"


def test_model_records_effective_backend(grey_odd):
    from parallel_convolution_tpu.models import ConvolutionModel

    m = _mesh((2, 2))
    with faults.injected("backend_compile:1"):
        with pytest.warns(degrade.BackendDegradedWarning):
            model = ConvolutionModel(filt="blur3", mesh=m, backend="pallas",
                                     fallback=True)
            got = model.run_image(grey_odd, 2)
    assert model.effective_backend == "shifted"
    want = oracle.run_serial_u8(grey_odd, filters.get_filter("blur3"), 2)
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- bench stamping


def test_bench_row_stamps_platform_and_effective_backend():
    filt = filters.get_filter("blur3")
    row = bench.bench_iterate((32, 32), filt, 2, mesh=_mesh((2, 2)),
                              backend="shifted", reps=1)
    assert row["platform"] == "cpu"
    assert row["effective_backend"] == "shifted"
    assert row["backend"] == "shifted"


def test_bench_fallback_row_records_degradation():
    filt = filters.get_filter("blur3")
    with faults.injected("backend_compile:1"):
        with pytest.warns(degrade.BackendDegradedWarning):
            row = bench.bench_iterate((32, 32), filt, 2, mesh=_mesh((2, 2)),
                                      backend="pallas", reps=1,
                                      fallback=True)
    assert row["backend"] == "pallas"          # what was asked for
    assert row["effective_backend"] == "shifted"  # what actually ran
    assert row["platform"] == "cpu"


# ------------------------------------------------- other fault sites


def test_halo_exchange_fault_site(grey_odd):
    filt = filters.get_filter("blur3")
    m = _mesh((2, 2))
    x = imageio.interleaved_to_planar(grey_odd).astype(np.float32)
    # fresh geometry so the runner is traced (not served from lru_cache)
    with faults.injected("halo_exchange:1"):
        with pytest.raises(faults.InjectedFault):
            step.sharded_iterate(x[:, :35, :29], filt, 1, mesh=m)


def test_io_read_fault_site(tmp_path):
    from parallel_convolution_tpu.utils import sharded_io

    img = imageio.generate_test_image(24, 40, "grey", seed=11)
    raw = tmp_path / "img.raw"
    imageio.write_raw(raw, img)
    m = _mesh((2, 2))
    with faults.injected("io_read:1"):
        with pytest.raises(Exception, match="injected fault at 'io_read'"):
            sharded_io.load_sharded(str(raw), 24, 40, "grey", m)


def test_device_probe_fault_site_recovers_under_retry(monkeypatch):
    from parallel_convolution_tpu.utils import platform

    monkeypatch.delenv(platform.SIM_DEVICES_ENV, raising=False)
    slept = []
    with faults.injected("device_probe:1"):
        live = retry.with_retry(
            platform.probe_device_count,
            retry.RetryPolicy(max_attempts=2, base_delay=0.01),
            sleep=slept.append)
    assert len(slept) == 1  # first probe died injected, second healed
    assert live == len(jax.devices())  # the child saw the same CPU mesh


# -------------------------------------------------------- supervisor


_FLAKY = """\
import os, sys
marker, out = sys.argv[1], sys.argv[2]
if not os.path.exists(marker):
    open(marker, "w").close()
    sys.exit(1)
open(out, "w").write("done leg")
"""


def test_supervisor_retries_transient_leg_to_done(tmp_path):
    out = tmp_path / "leg.out.artifact"
    leg = Leg(name="flaky",
              cmd=[sys.executable, "-c", _FLAKY,
                   str(tmp_path / "marker"), str(out)],
              done_file=str(out), done_pattern="done")
    sup = Supervisor([leg], tmp_path / "state",
                     policy=retry.RetryPolicy(max_attempts=3,
                                              base_delay=0.01),
                     sleep=lambda s: None, log=lambda m: None)
    assert sup.run() == 0
    status = json.loads((tmp_path / "state" / "status.json").read_text())
    assert status["legs"]["flaky"]["state"] == "done"
    assert status["legs"]["flaky"]["attempts"] == 2
    assert status["halt"] is None
    # idempotent re-run: completed legs are skipped
    assert sup.run() == 0


def test_supervisor_terminal_pattern_halts_queue(tmp_path):
    second = tmp_path / "second.txt"
    legs = [
        Leg(name="mismatch",
            cmd=[sys.executable, "-c",
                 "print('\"magic_round_guard\": \"MISMATCH\"')"],
            done_file=str(tmp_path / "never"),
            terminal_pattern='"magic_round_guard": "MISMATCH"'),
        Leg(name="after",
            cmd=[sys.executable, "-c",
                 f"open({str(second)!r}, 'w').write('x')"]),
    ]
    sup = Supervisor(legs, tmp_path / "state",
                     policy=retry.RetryPolicy(max_attempts=3,
                                              base_delay=0.01),
                     sleep=lambda s: None, log=lambda m: None)
    assert sup.run() == 2
    assert (tmp_path / "state" / "HALT").exists()
    assert not second.exists()  # the queue stopped at the terminal leg
    status = json.loads((tmp_path / "state" / "status.json").read_text())
    assert status["halt"]["leg"] == "mismatch"
    # a later run refuses while the sentinel stands
    assert sup.run() == 2


def test_supervisor_exhausted_leg_continues_queue(tmp_path):
    done2 = tmp_path / "two.txt"
    legs = [
        Leg(name="hopeless", cmd=[sys.executable, "-c", "raise SystemExit(1)"]),
        Leg(name="fine",
            cmd=[sys.executable, "-c", f"open({str(done2)!r}, 'w').write('y')"],
            done_file=str(done2)),
    ]
    sup = Supervisor(legs, tmp_path / "state",
                     policy=retry.RetryPolicy(max_attempts=2,
                                              base_delay=0.01),
                     sleep=lambda s: None, log=lambda m: None)
    assert sup.run() == 1
    status = json.loads((tmp_path / "state" / "status.json").read_text())
    assert status["legs"]["hopeless"]["state"] == "exhausted"
    assert status["legs"]["fine"]["state"] == "done"


def test_supervisor_sleeps_the_policy_schedule(tmp_path):
    """One retry implementation: the supervisor's backoff must equal
    RetryPolicy.delays() — not a private derivation of it."""
    policy = retry.RetryPolicy(max_attempts=3, base_delay=0.5, seed=9)
    slept = []
    leg = Leg(name="hopeless",
              cmd=[sys.executable, "-c", "raise SystemExit(1)"])
    sup = Supervisor([leg], tmp_path / "state", policy=policy,
                     sleep=slept.append, log=lambda m: None)
    assert sup.run() == 1
    assert slept == policy.delays()


def test_legs_from_json_validation():
    legs = legs_from_json(
        '[{"name": "a", "cmd": ["true"], "done_file": "x"}]')
    assert legs[0].name == "a"
    with pytest.raises(ValueError, match="unknown leg field"):
        legs_from_json('[{"name": "a", "cmd": ["true"], "bogus": 1}]')
    with pytest.raises(ValueError, match="JSON list"):
        legs_from_json('{"name": "a"}')


# --------------------------------------- end-to-end fault-soak drill


def test_fault_soak_trial_end_to_end(tmp_path):
    """One scripts/soak.py --fault-trial child: inject a checkpoint tear,
    crash, resume, byte-compare — the unit the supervised fault soak
    (--faults N) fans out."""
    from parallel_convolution_tpu.utils.platform import child_env_cpu

    out = tmp_path / "trial.json"
    repo = Path(__file__).resolve().parents[1]
    p = subprocess.run(
        [sys.executable, str(repo / "scripts" / "soak.py"),
         "--fault-trial", "checkpoint_write_shard:2",
         "--trial-seed", "3", "--trial-out", str(out)],
        env=child_env_cpu(8), capture_output=True, text=True, timeout=300,
        cwd=repo)
    assert p.returncode == 0, p.stderr[-2000:]
    row = json.loads(out.read_text())
    assert row["ok"] is True
    assert row["crashed"] is not None  # the injected kill really fired
    assert row["fired"] == [["checkpoint_write_shard", 2]]
