"""The trace reduction on a recorded TPU v5e trace and on made-up spans.

The fixture is 8192^2 grey, blur3, pallas_sep/bf16/fuse 32, 100 iterations
on one v5e chip: three ``fused_iterate_pallas.5`` launches and one
``fused_iterate_pallas.4``, each after the step layer's pad/concatenate.
"""

import os

import pytest

from benchmark import run, trace_reduce as tr, work

FIXTURE = os.path.join(run.BENCH, "fixtures", "flagship_8192_fuse32.xplane.pb")


@pytest.fixture(scope="module")
def fixture_trace():
    return tr.load(FIXTURE)


@pytest.fixture(scope="module")
def body(fixture_trace):
    dev = fixture_trace.devices[0]
    (span,) = [m for m in dev.modules if tr.module_name(m) == "jit_body"]
    return span


def test_fixture_has_one_tpu_device(fixture_trace):
    assert [d.name for d in fixture_trace.devices] == ["/device:TPU:0"]


def test_fixture_kernels_glue_and_busy(fixture_trace, body):
    s = tr.summarize(fixture_trace.devices[0], body.start, body.end)
    assert s.kernel_launches == 4
    assert s.kernel / 1e6 == pytest.approx(48.40, abs=0.01)
    assert s.glue / 1e6 == pytest.approx(5.32, abs=0.01)
    assert s.busy / 1e6 == pytest.approx(53.7, abs=0.05)
    assert s.busy <= s.window
    assert s.collective == 0 and s.collective_exposed == 0


def test_fixture_while_is_a_container_not_busy(fixture_trace, body):
    dev = fixture_trace.devices[0]
    assert [tr.op_name(c) for c in dev.containers] == ["while"]
    (loop,) = dev.containers
    assert loop.dur / 1e6 == pytest.approx(50.35, abs=0.01)
    assert all(tr.op_name(o) != "while" for o in dev.ops)
    # Summing leaves never exceeds the module: the container is not added.
    leaves = tr.within(dev.ops, body.start, body.end)
    assert sum(o.dur for o in leaves) <= body.dur


def test_fixture_kernels_found_by_kind_not_name(fixture_trace, body):
    prog = tr.program_ops(fixture_trace.devices[0], body.start, body.end)
    names = sorted({tr.op_name(o) for o in prog if tr.is_kernel(o)})
    assert names == ["fused_iterate_pallas.4", "fused_iterate_pallas.5"]


def test_fixture_top_ops(fixture_trace, body):
    top = tr.top_ops(fixture_trace, body.start, body.end)
    assert top[0][0] == "fused_iterate_pallas.5"
    assert top[0][1] == pytest.approx(0.04658, abs=1e-5)
    assert len(top) <= 10


def test_fixture_roofline_share_reads_low_by_construction(fixture_trace,
                                                          body):
    s = tr.summarize(fixture_trace.devices[0], body.start, body.end)
    pixels = 8192 * 8192
    least, bound = work.least_time_s(work.stencil_ops(9, pixels, 100),
                                     work.image_bytes(pixels),
                                     work.peak_for("TPU v5 lite"))
    assert bound == "compute"
    assert 100 * least / (s.kernel / 1e9) == pytest.approx(1.27, abs=0.02)


def S(name, a, b):
    return tr.Span(name, a, b)


def test_union_subtract_gaps():
    u = tr.union([S("a", 0, 10), S("b", 5, 20), S("c", 30, 40)])
    assert u == [(0, 20), (30, 40)]
    assert tr.total(u) == 30
    assert tr.subtract(u, [(8, 12), (35, 50)]) == [(0, 8), (12, 20),
                                                   (30, 35)]
    assert tr.gaps(u, -5, 45) == [(-5, 0), (20, 30), (40, 45)]
    assert tr.clip(u, 10, 35) == [(10, 20), (30, 35)]


def test_split_containers_nested():
    ops = [S("%while = x", 0, 100), S("%a = y", 0, 40), S("%b = z", 50, 90),
           S("%c = w", 120, 130)]
    leaves, containers = tr.split_containers(ops)
    assert [s.name for s in containers] == ["%while = x"]
    assert [s.name for s in leaves] == ["%a = y", "%b = z", "%c = w"]


@pytest.mark.parametrize("text, want", [
    ("%collective-permute.3 = u8[1,8,2048]{2,1,0} collective-permute(u8[1,8,"
     "2048]{2,1,0} %slice.1), source_target_pairs={{0,1}}", True),
    ("%collective-permute-done.1 = bf16[8,128] collective-permute-done("
     "(bf16[8,128], bf16[8,128]) %collective-permute-start.1)", True),
    ("%all-reduce.2 = f32[] all-reduce(f32[] %x), to_apply=%add", True),
    ("%pad.20 = bf16[1,8256,8320] pad(bf16[1,8256,8256] %concatenate.7)",
     False),
    ('%fused_iterate_pallas.5 = bf16[1,8192,8192] custom-call(s32[2] %b), '
     'custom_call_target="tpu_custom_call"', False),
])
def test_collectives_found_by_opcode(text, want):
    assert tr.is_collective(S(text, 0, 1)) is want


def test_exposed_collective_time():
    dev = tr.Device("/device:TPU:0", [S("jit_body(1)", 0, 100)],
                    [S("%collective-permute.1 = u8[] collective-permute(u8[] "
                       "%a)", 0, 30),
                     S('%k = u8[] custom-call(u8[] %b), custom_call_target='
                       '"tpu_custom_call"', 20, 60),
                     S("%collective-permute.2 = u8[] collective-permute(u8[] "
                       "%c)", 70, 80)], [])
    s = tr.summarize(dev, 0, 100)
    assert s.collective == 40
    assert s.collective_exposed == 30  # 0-20 and 70-80
    assert s.kernel == 40 and s.glue == 40
    assert s.busy == 70


def test_idle_gaps_named_by_innermost_host_span():
    dev = tr.Device("/device:TPU:0", [], [S("%a = x", 10, 20),
                                          S("%b = y", 40, 50)], [])
    host = [S("bench.window", 0, 60), S("bench.job", 0, 60),
            S("bench.fence", 15, 35), S("bench.run_planar", 35, 45)]
    trace = tr.Trace([dev], host)
    gaps = dict(tr.idle_by_host(trace, 0, 60))
    assert gaps == {"bench.job (2 gaps)": pytest.approx(20e-9),
                    "bench.fence (1 gaps)": pytest.approx(20e-9)}
    assert tr.window_of(trace) == (0, 60)
