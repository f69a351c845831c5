"""Round 11: unified observability layer.

Covers the acceptance surface of the obs PR:

* registry semantics (typed metrics, labels, get-or-create, shape guard);
* disabled mode (``PCTPU_OBS=0``): nothing recorded, near-zero overhead
  (the perf guard);
* event-log schema + atomic rotation with seq continuity;
* Prometheus exposition round-trip (render → parse) and the serving
  ``/metrics`` surfaces;
* exchange-byte accounting vs an independent analytic derivation, and
  the same numbers flowing out of ``iterate_prepared`` and bench rows;
* PhaseTimer thread-safety + tracing edge cases (nested-path collisions
  in ``to_row``, re-entrant phases, fence exceptions);
* supervisor ledger schema_version/heartbeat + tolerant old-ledger read.
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.obs import attribution, events, metrics
from parallel_convolution_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test sees an enabled, empty registry and no global event log;
    the prior state is restored afterwards (other test modules rely on
    module-level counters accumulating silently)."""
    was_enabled = metrics.enabled()
    metrics.set_enabled(True)
    metrics.reset()
    events.deconfigure()
    yield
    events.deconfigure()
    metrics.reset()
    metrics.set_enabled(was_enabled)


# ------------------------------------------------------------- registry
def test_counter_gauge_histogram_semantics():
    c = metrics.counter("c_total", "x", ("who",))
    c.inc(who="a")
    c.inc(2.5, who="a")
    c.inc(who="b")
    assert c.value(who="a") == 3.5 and c.value(who="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, who="a")          # counters are monotonic
    with pytest.raises(ValueError):
        c.inc(nope="a")             # labels must match declaration

    g = metrics.gauge("g", "", ("k",))
    g.set(5, k="x")
    g.set(2, k="x")
    assert g.value(k="x") == 2.0    # last-write-wins
    g.max(7, k="x")
    g.max(3, k="x")
    assert g.value(k="x") == 7.0    # high-water mark

    h = metrics.histogram("h_seconds", "", (), buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    s = h._series_snapshot()[0]
    assert s["count"] == 4 and s["counts"] == [1, 1, 1, 1]
    assert s["sum"] == pytest.approx(5.555)
    assert 0.01 < h.quantile(0.5) <= 0.1
    # +Inf is the IMPLICIT last bucket: an explicit one would render a
    # duplicate le="+Inf" sample, so non-finite bounds are rejected.
    import math

    with pytest.raises(ValueError, match="finite"):
        metrics.histogram("h_bad", buckets=(1.0, math.inf))
    with pytest.raises(ValueError, match="finite"):
        metrics.histogram("h_bad2", buckets=())


def test_registry_get_or_create_and_shape_guard():
    a = metrics.counter("same_total", "", ("x",))
    b = metrics.counter("same_total", "", ("x",))
    assert a is b                   # handles converge on one series set
    with pytest.raises(ValueError):
        metrics.counter("same_total", "", ("y",))   # labelnames drifted
    with pytest.raises(ValueError):
        metrics.gauge("same_total")                 # kind drifted


def test_mirrored_stats_is_a_dict_and_a_gauge():
    g = metrics.gauge("stats_g", "", ("key",))
    ms = metrics.MirroredStats(g, initial={"hits": 0, "misses": 0})
    ms["hits"] += 3
    ms["misses"] = 7
    # The legacy dict surface is intact...
    assert dict(ms) == {"hits": 3, "misses": 7}
    assert ms["hits"] == 3 and len(ms) == 2 and set(ms) == {"hits", "misses"}
    # ...and the same values are registry series.
    assert g.value(key="hits") == 3.0 and g.value(key="misses") == 7.0


def test_mirrored_stats_dict_survives_disabled_mode():
    g = metrics.gauge("stats_g2", "", ("key",))
    ms = metrics.MirroredStats(g, initial={"n": 0})
    metrics.set_enabled(False)
    ms["n"] += 5
    assert ms["n"] == 5             # serving semantics never depend on obs
    assert g.value(key="n") == 0.0  # but the mirror went dark


# -------------------------------------------------------- disabled mode
def test_disabled_mode_records_nothing():
    metrics.set_enabled(False)
    c = metrics.counter("dark_total", "", ("a",))
    c.inc(a="x")
    metrics.histogram("dark_s").observe(1.0)
    metrics.gauge("dark_g").set(3)
    snap = metrics.snapshot()
    assert snap["enabled"] is False
    assert all(not m["series"] for m in snap["metrics"])
    # events.emit is also a no-op even with a log installed
    log = events.configure("/tmp/_pctpu_dark.jsonl")
    events.emit("retry", attempt=1)
    assert not log.path.exists() or log.path.stat().st_size == 0


def test_disabled_mode_overhead_is_near_zero():
    """The PCTPU_OBS=0 perf guard: a disabled inc must be one load + one
    branch.  Bounds are deliberately generous (CI jitter) — the test
    fails on a pathological regression (locking, allocation, formatting
    on the disabled path), not on scheduler noise."""
    c = metrics.counter("perf_total", "", ("a",))
    n = 50_000
    metrics.set_enabled(True)
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc(a="x")
    enabled_s = time.perf_counter() - t0
    metrics.set_enabled(False)
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc(a="x")
    disabled_s = time.perf_counter() - t0
    assert disabled_s < 0.5                      # < 10 µs/call, absolute
    assert disabled_s < enabled_s * 1.5 + 0.01   # never costlier than on
    assert c.value(a="x") == n                   # only the enabled half


# ------------------------------------------------------------ event log
def test_event_log_schema_and_unknown_kind(tmp_path):
    log = events.configure(tmp_path / "ev.jsonl")
    rec = events.emit("compile", backend="shifted")
    recs = events.read_events(log.path)
    assert len(recs) == 1
    assert events.validate_event(recs[0]) == []
    r = recs[0]
    assert r["seq"] == 1 and r["kind"] == "compile"
    assert isinstance(r["ts"], float) and isinstance(r["perf"], float)
    with pytest.raises(ValueError, match="unknown event kind"):
        log.emit("typo_kind")
    with pytest.raises(ValueError, match="reserved"):
        log.emit("compile", seq=99)
    # validate_event names each problem
    assert events.validate_event({"kind": "nope"})
    assert events.validate_event([1, 2]) == ["not an object: list"]


def test_event_log_rotation_atomic_and_seq_continuous(tmp_path):
    log = events.EventLog(tmp_path / "ev.jsonl", max_bytes=4096, keep=2)
    for i in range(300):
        log.emit("retry", attempt=i, pad="x" * 60)
    gens = log.generations()
    assert len(gens) == 3            # .2, .1, live — older gens dropped
    recs = events.read_events(log.path)
    # Stitched timeline: strictly consecutive seq, ending at the total.
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(seqs[0], 301))
    assert all(events.validate_event(r) == [] for r in recs)


def test_module_emit_without_log_is_noop():
    events.emit("retry", attempt=1)  # no log configured: must not raise


def test_event_log_survives_external_rotation(tmp_path):
    """A second process rotating the shared file must not leave this
    writer streaming into the renamed `.1` generation."""
    import os

    log = events.EventLog(tmp_path / "ev.jsonl")
    log.emit("retry", attempt=1)
    # Simulate the sibling's rotation: rename the live file away.
    os.replace(log.path, log.path.with_name("ev.jsonl.1"))
    log.emit("retry", attempt=2)
    live = events.read_events(log.path, include_rotated=False)
    assert [r["attempt"] for r in live] == [2]   # landed in the NEW live
    both = events.read_events(log.path)
    assert [r["attempt"] for r in both] == [1, 2]
    assert all(r["pid"] == os.getpid() for r in both)


def test_rotation_survives_sibling_stealing_live(tmp_path):
    """The writer's OWN rotation racing a sibling's: the live file can
    vanish between our size check and our ``os.replace`` (both processes
    rotate the same path).  Pre-round-13 this raised FileNotFoundError
    out of ``emit`` and LOST the line; now a vanished source degrades to
    'already moved' and the write proceeds in a fresh generation."""
    import os

    log = events.EventLog(tmp_path / "ev.jsonl", max_bytes=4096, keep=2)
    log.emit("retry", attempt=1)
    # The sibling wins the race: live is renamed away while we hold an
    # open fd and believe the file still exists.
    os.replace(log.path, tmp_path / "stolen.jsonl")
    with log._lock:
        log._rotate_locked()         # must not raise
    log.emit("retry", attempt=2)     # and the stream continues
    recs = events.read_events(log.path)
    assert [r["attempt"] for r in recs] == [2]
    stolen = events.read_events(tmp_path / "stolen.jsonl",
                                include_rotated=False)
    assert [r["attempt"] for r in stolen] == [1]   # nothing lost


def test_event_log_multithread_rotation_stress(tmp_path):
    """N writer threads across MANY forced rotations, with an external
    actor stealing the live file mid-stream (a sibling process's
    rotation): no writer may crash, no line may be lost, and each pid's
    seq stream must stay contiguous across every file the lines landed
    in."""
    import os

    # keep must exceed the WORST-CASE rotation count or the test races
    # its own mover thread: 6x200 lines x ~200 B / 4096 B/segment is up
    # to ~60 rotations, and with keep=50 a starved mover let the
    # writer's own (correct) rotation delete generation 51+ — a flaky
    # false failure on loaded machines.  120 gives 2x headroom while
    # still forcing dozens of rotations.
    log = events.EventLog(tmp_path / "ev.jsonl", max_bytes=4096,
                          keep=120)
    n_threads, n_lines = 6, 200
    stop = threading.Event()
    errors: list[BaseException] = []

    def mover():
        k = 0
        while not stop.is_set():
            try:
                os.replace(log.path, tmp_path / f"moved.{k}.stolen")
                k += 1
            except OSError:
                pass
            time.sleep(0.0002)

    def writer(w):
        try:
            for i in range(n_lines):
                log.emit("retry", attempt=i, w=w, pad="x" * 120)
        except BaseException as e:  # noqa: BLE001 — the assertion target
            errors.append(e)

    mv = threading.Thread(target=mover, daemon=True)
    mv.start()
    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    mv.join()
    assert errors == []
    recs = []
    for p in sorted(tmp_path.iterdir()):
        for n, line in enumerate(p.read_text().splitlines(), 1):
            if line.strip():
                recs.append(json.loads(line))
    assert all(events.validate_event(r) == [] for r in recs)
    # Zero lost lines, zero duplicates, per-pid seq contiguous: the
    # stitched multiset of seqs across EVERY generation + stolen file is
    # exactly 1..total.
    seqs = sorted(r["seq"] for r in recs)
    assert seqs == list(range(1, n_threads * n_lines + 1))


# ----------------------------------------------------------- exposition
def test_exposition_round_trip():
    c = metrics.counter("rt_total", "help text", ("name",))
    c.inc(3, name='we"ird\nlabel')
    h = metrics.histogram("rt_seconds", "", ("b",), buckets=(0.1, 1.0))
    h.observe(0.05, b="z")
    h.observe(5.0, b="z")
    text = metrics.render_text()
    assert "# TYPE rt_total counter" in text
    assert "# HELP rt_total help text" in text
    parsed = metrics.parse_text(text)
    assert parsed["rt_total"] == [({"name": 'we"ird\nlabel'}, 3.0)]
    # A literal backslash-n (repr'd exception text) must round-trip —
    # sequential unescape passes corrupted it to backslash-newline.
    c2 = metrics.counter("esc_total", "", ("cause",))
    c2.inc(cause='OSError("bad\\npath")')   # literal backslash + n
    reparsed = metrics.parse_text(metrics.render_text())
    assert reparsed["esc_total"] == [({"cause": 'OSError("bad\\npath")'},
                                      1.0)]
    buckets = {s[0]["le"]: s[1] for s in parsed["rt_seconds_bucket"]}
    assert buckets == {"0.1": 1.0, "1": 1.0, "+Inf": 2.0}  # cumulative
    assert parsed["rt_seconds_count"] == [({"b": "z"}, 2.0)]
    with pytest.raises(ValueError):
        metrics.parse_text("malformed{ 3")


def test_in_process_metrics_surface(monkeypatch):
    from parallel_convolution_tpu.serving import frontend

    metrics.counter("srv_total").inc()
    status, text = 200, frontend.metrics_text()
    assert "srv_total 1" in text
    metrics.set_enabled(False)
    assert frontend.metrics_text().startswith("#")  # still valid exposition


# ------------------------------------------- exchange-byte accounting
def test_halo_bytes_vs_independent_formula():
    # Independent derivation: zero boundary, R rows of C columns; the
    # row phase moves (R-1)*C slabs of (channels*d*bw*B) bytes each way;
    # the column phase moves (C-1)*R slabs cut from the ROW-PADDED block,
    # height bh+2d.
    grid, block, r, fuse, ch, B = (2, 4), (24, 16), 1, 2, 3, 4
    d = r * fuse
    bh, bw = block
    want_ns = (grid[0] - 1) * grid[1] * ch * d * bw * B
    want_ew = (grid[1] - 1) * grid[0] * ch * d * (bh + 2 * d) * B
    got = attribution.halo_bytes_per_round(grid, block, r, fuse, ch, "f32")
    assert got["north"] == got["south"] == want_ns
    assert got["east"] == got["west"] == want_ew
    assert got["total"] == 2 * (want_ns + want_ew)
    # bf16 halves every direction
    half = attribution.halo_bytes_per_round(grid, block, r, fuse, ch, "bf16")
    assert half["total"] * 2 == got["total"]
    # periodic closes the ring: R senders per axis instead of R-1
    per = attribution.halo_bytes_per_round(grid, block, r, fuse, ch, "f32",
                                           boundary="periodic")
    assert per["north"] == grid[0] * grid[1] * ch * d * bw * B
    # 1x1 mesh: no collective, no bytes
    assert attribution.halo_bytes_per_round(
        (1, 1), block, r, fuse, ch, "f32")["total"] == 0
    # a 1-long axis moves nothing even under periodic (identity wrap)
    one_row = attribution.halo_bytes_per_round(
        (1, 4), block, r, fuse, ch, "f32", boundary="periodic")
    assert one_row["north"] == 0 and one_row["east"] > 0


def test_halo_bytes_total_accounts_the_tail_round():
    # 10 iterations at fuse 4 = 2 full rounds (depth 4r) + 1 tail (2r).
    grid, block, r, ch = (2, 2), (32, 32), 1, 1
    full = attribution.halo_bytes_per_round(grid, block, r, 4, ch, "f32")
    tail = attribution.halo_bytes_per_round(grid, block, r, 2, ch, "f32")
    tot = attribution.halo_bytes_total(grid, block, r, 4, 10, ch, "f32")
    assert tot["rounds"] == 3
    for dname in attribution.DIRECTIONS:
        assert tot[dname] == 2 * full[dname] + tail[dname]


def test_iterate_prepared_feeds_halo_counters(grey_small):
    from parallel_convolution_tpu.ops import filters
    from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
    from parallel_convolution_tpu.utils import imageio

    m = mesh_lib.make_grid_mesh(jax.devices()[:8], (2, 4))
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, block_hw = step._prepare(
        x, m, step.resolve_launch(m, filt, x.shape))
    iters = 3
    step.iterate_prepared(xs, filt, iters, m, valid_hw)
    want = attribution.halo_bytes_total(
        (2, 4), block_hw, filt.radius, 1, iters, 1, "f32")
    c = metrics.counter("pctpu_halo_bytes_total", "", ("backend",
                                                       "direction"))
    for dname in attribution.DIRECTIONS:
        assert c.value(backend="shifted", direction=dname) == want[dname]
    assert metrics.counter(
        "pctpu_iterations_total", "",
        ("backend",)).value(backend="shifted") == iters
    # iterate_prepared dispatches async, so it must NOT feed wall-based
    # series (that would require a serializing fence) — byte/round
    # counters only.  Wall series come from fenced call sites (bench,
    # serving, converge).
    h = metrics.histogram("pctpu_step_seconds", "", ("backend",))
    assert h.quantile(0.5, backend="shifted") is None
    step.sharded_converge(
        imageio.interleaved_to_planar(grey_small).astype(np.float32),
        filt, 1e-3, 4, check_every=2, mesh=m)
    assert h.quantile(0.5, backend="shifted") is not None  # fenced caller


def test_bench_row_carries_attribution(grey_small):
    from parallel_convolution_tpu.ops import filters
    from parallel_convolution_tpu.parallel import mesh as mesh_lib
    from parallel_convolution_tpu.utils import bench

    m = mesh_lib.make_grid_mesh(jax.devices()[:8], (2, 4))
    row = bench.bench_iterate((48, 64), filters.get_filter("blur3"), 2,
                              mesh=m, reps=1)
    assert 0.0 <= row["exchange_fraction"] <= 1.0
    hb = row["halo_bytes"]
    want = attribution.halo_bytes_total(
        (2, 4), (24, 16), 1, row["fuse"], 2, 1, "f32")
    assert {d: hb[d] for d in attribution.DIRECTIONS} == {
        d: want[d] for d in attribution.DIRECTIONS}
    # the drift series landed, labeled with the tuning plan key
    snap = metrics.snapshot()
    drift = [mm for mm in snap["metrics"]
             if mm["name"] == "pctpu_plan_drift_ratio"][0]
    assert drift["series"] and all(
        s["labels"]["backend"] == "shifted" for s in drift["series"])


# --------------------------------------------------- PhaseTimer hardening
def test_phase_timer_thread_safety():
    """A timer SHARED across threads (the batcher-worker + HTTP-handler
    shape) must keep per-thread nesting and exact counts — pre-round-11
    the shared ``_stack`` interleaved and corrupted paths."""
    t = tracing.PhaseTimer()
    n_threads, n_iter = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for _ in range(n_iter):
            with t.phase("outer"):
                with t.phase("inner"):
                    pass

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # Exactly two paths — any stack interleaving would have minted paths
    # like outer/outer or inner/outer.
    assert set(t.walls) == {"outer", "outer/inner"}
    assert t.counts["outer"] == n_threads * n_iter
    assert t.counts["outer/inner"] == n_threads * n_iter


def test_phase_timer_to_row_collision_sums():
    t = tracing.PhaseTimer()
    with t.phase("a"):
        with t.phase("b"):
            time.sleep(0.001)
    with t.phase("a_b"):   # flattens to the same row key as a/b
        time.sleep(0.001)
    row = t.to_row()
    assert set(row) == {"phase_a_s", "phase_a_b_s"}
    # summed, not overwritten: the collided key carries BOTH walls
    assert row["phase_a_b_s"] == pytest.approx(
        t.wall("a/b") + t.wall("a_b"), abs=1e-5)


def test_phase_timer_reentrant_same_name():
    t = tracing.PhaseTimer()
    with t.phase("x"):
        with t.phase("x"):
            pass
    assert set(t.walls) == {"x", "x/x"}
    assert t.counts["x"] == 1 and t.counts["x/x"] == 1


def test_phase_timer_fence_exception_leaves_stack_balanced():
    t = tracing.PhaseTimer()
    dead = jax.numpy.ones((4,))
    dead.delete()
    with pytest.raises(RuntimeError):
        with t.phase("outer"):
            with t.phase("inner", fence=dead):
                pass
    # Both phases recorded despite the fence raising, and the stack is
    # balanced: the next phase lands top-level, not under a ghost parent.
    assert t.counts["outer"] == 1 and t.counts["outer/inner"] == 1
    with t.phase("after"):
        pass
    assert "after" in t.walls and "outer/after" not in t.walls


# -------------------------------------------------- supervisor ledger
def test_supervisor_ledger_schema_and_heartbeat(tmp_path):
    import sys

    from parallel_convolution_tpu.resilience.retry import RetryPolicy
    from parallel_convolution_tpu.resilience.supervisor import (
        LEDGER_SCHEMA, Leg, Supervisor, read_ledger,
    )

    touches = []

    class Spy(Supervisor):
        def _touch_heartbeat(self, leg_name=""):
            touches.append(leg_name)
            super()._touch_heartbeat(leg_name)

    leg = Leg(name="nap",
              cmd=[sys.executable, "-c", "import time; time.sleep(0.8)"])
    sup = Spy([leg], tmp_path / "state",
              policy=RetryPolicy(max_attempts=1), sleep=lambda s: None,
              log=lambda m: None, heartbeat_every=0.2)
    assert sup.run() == 0
    ledger = read_ledger(tmp_path / "state" / "status.json")
    assert ledger["schema_version"] == LEDGER_SCHEMA
    assert ledger["heartbeat"] and ledger["heartbeat_unix"] > 0
    assert ledger["legs"]["nap"]["state"] == "done"
    # The heartbeat was refreshed BETWEEN polls while the leg slept — the
    # running-vs-hung watcher signal.
    assert len(touches) >= 2


def test_read_ledger_tolerates_old_schema(tmp_path):
    from parallel_convolution_tpu.resilience.supervisor import read_ledger

    old = {"legs": {"a": {"state": "done"}}, "halt": None,
           "updated": "2026-01-01T00:00:00Z"}
    p = tmp_path / "status.json"
    p.write_text(json.dumps(old))
    got = read_ledger(p)
    assert got["schema_version"] == 1          # pre-round-11 default
    assert got["heartbeat"] == "2026-01-01T00:00:00Z"  # best old signal
    assert got["heartbeat_unix"] is None
    with pytest.raises(FileNotFoundError):
        read_ledger(tmp_path / "missing.json")


# ----------------------------------------------- resilience telemetry
def test_retry_and_fault_telemetry(tmp_path):
    from parallel_convolution_tpu.resilience import faults
    from parallel_convolution_tpu.resilience.retry import (
        RetryPolicy, with_retry,
    )

    events.configure(tmp_path / "ev.jsonl")
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TimeoutError("blip")
        return "ok"

    assert with_retry(flaky, RetryPolicy(max_attempts=5, base_delay=0.0),
                      sleep=lambda s: None) == "ok"
    assert metrics.counter(
        "pctpu_retries_total", "",
        ("error",)).value(error="TimeoutError") == 2

    with faults.injected("io_read:1"):
        with pytest.raises(faults.InjectedFault):
            faults.fault_point("io_read")
    assert metrics.counter(
        "pctpu_faults_fired_total", "",
        ("site",)).value(site="io_read") == 1

    kinds = [r["kind"] for r in events.read_events(tmp_path / "ev.jsonl")]
    assert kinds.count("retry") == 2 and "fault_trigger" in kinds


def test_quarantine_counter_names_cause(tmp_path, grey_small):
    from parallel_convolution_tpu.ops import filters
    from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
    from parallel_convolution_tpu.utils import checkpoint, imageio

    m = mesh_lib.make_grid_mesh(jax.devices()[:4], (2, 2))
    filt = filters.get_filter("blur3")
    x = imageio.interleaved_to_planar(grey_small).astype(np.float32)
    xs, valid_hw, _ = step._prepare(
        x, m, step.resolve_launch(m, filt, x.shape))
    checkpoint.save_state(tmp_path, xs, {
        "grid": [2, 2], "shape": list(xs.shape), "iters_done": 4,
        "valid_hw": list(valid_hw)})
    (tmp_path / "it_00000004" / "shard_0_0.npy").unlink()  # damage it
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.load_state(tmp_path, m)
    assert metrics.counter(
        "pctpu_quarantines_total", "",
        ("cause",)).value(cause="missing") == 1
    # a clean save left its duration/bytes series behind
    assert metrics.counter(
        "pctpu_checkpoint_bytes_total", "", ("op",)).value(op="save") > 0


# ------------------------------------------------------ serving spine
def test_service_stats_flow_through_registry(grey_small):
    from parallel_convolution_tpu.parallel import mesh as mesh_lib
    from parallel_convolution_tpu.serving.service import (
        ConvolutionService, Request,
    )

    m = mesh_lib.make_grid_mesh(jax.devices()[:8], (2, 4))
    svc = ConvolutionService(m)
    try:
        r = svc.submit(Request(image=grey_small, iters=2))
        assert r.ok
        bad = svc.submit(Request(image=grey_small.astype(np.float32)))
        assert not bad.ok and bad.reason == "invalid"
    finally:
        svc.close()
    # One spine: the legacy dicts and the registry agree.
    g = metrics.gauge("pctpu_service_stats", "", ("key",))
    assert g.value(key="completed") == svc.stats["completed"] == 1
    assert g.value(key="rejected_invalid") == 1
    adm = metrics.counter("pctpu_admission_total", "", ("outcome",))
    assert adm.value(outcome="completed") == 1
    assert adm.value(outcome="invalid") == 1
    eng = metrics.gauge("pctpu_engine_stats", "", ("key",))
    assert eng.value(key="compiles") == svc.engine.stats["compiles"]
    # per-request phase histogram has every serving phase
    h = metrics.histogram("pctpu_request_phase_seconds", "",
                          ("phase", "backend"))
    for phase in ("queue", "compile", "device", "copy_in", "copy_out",
                  "total"):
        assert h.quantile(0.5, phase=phase, backend="shifted") is not None
