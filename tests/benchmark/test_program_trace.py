"""The readers of the program's own spans and scopes, on made-up traces,
and the existing metrics on the recorded v5e fixture."""

import os

import pytest

from benchmark import program_trace as pt, run, trace_reduce as tr, work

FIXTURE = os.path.join(run.BENCH, "fixtures", "flagship_8192_fuse32.xplane.pb")
KERNEL = 'custom_call_target="tpu_custom_call"'


def S(name, a, b):
    return tr.Span(name, a, b)


def H(name, a, b, **args):
    return pt.HostSpan(name, a, b, args)


def device(name="/device:TPU:0"):
    # One launch of the iterate program over [0, 100): a halo pad, a
    # kernel_io pad, the kernel, an unscoped convert; idle 30-40, 90-100.
    return tr.Device(name, [S("jit_body(7)", 0, 100)],
                     [S("%pad.1 = bf16[] pad()", 0, 10),
                      S("%concatenate.2 = bf16[] concatenate()", 10, 20),
                      S("%pad.3 = bf16[] pad()", 20, 30),
                      S(f"%k.4 = bf16[] custom-call(), {KERNEL}", 40, 80),
                      S("%convert.5 = f32[] convert()", 80, 90)], [])


SCOPES = {"pad.1": "pctpu.halo", "concatenate.2": "pctpu.halo",
          "pad.3": "pctpu.kernel_io", "k.4": "pctpu.kernel_io",
          "convert.5": ""}
HOST = [H("pctpu.run_planar", 25, 45), H("pctpu.resolve", 26, 27),
        H("pctpu.step.prepare", 27, 30), H("pctpu.step.build", 30, 33),
        H("pctpu.step.dispatch", 33, 36, exchanges=13, halo_bytes=6603776),
        H("pctpu.step.crop", 36, 44),
        H("pctpu.run_planar", 85, 99), H("pctpu.step.dispatch", 86, 90,
                                         exchanges=13,
                                         halo_bytes=6603776)]


def ctx_of(cell="edge5_grey_2k.mesh2x2", devices=None, host=HOST,
           scopes=SCOPES, jobs=2, module="jit_body"):
    c = run.Context(cell=run.cell_of(run.load_spec(), cell), jobs=jobs,
                    window_s=1e-7, setup_s=0.0, pixels_per_job=1,
                    trace=tr.Trace([device()] if devices is None
                                   else devices, []), lo=0, hi=100)
    c.program = pt.ProgramTrace(list(host), scopes, module)
    return c


def read(name, ctx):
    return run.metric_reader(name).read(ctx)


def test_host_path_idle_is_idle_inside_run_planar():
    # idle 30-40 and 90-100; run_planar covers 25-45 and 85-99:
    # 10 + 9 ns over 2 jobs.
    assert read("host_path_idle_ms", ctx_of()) == pytest.approx(9.5e-6)
    two = ctx_of(devices=[device(), device("/device:TPU:1")])
    assert read("host_path_idle_ms.short_jobs", two) == pytest.approx(9.5e-6)


def test_step_host_sums_the_step_spans_per_job():
    # prepare 3 + build 3 + dispatch 3 + crop 8 + dispatch 4 = 21 ns.
    assert read("step_host_ms", ctx_of()) == pytest.approx(10.5e-6)


def test_halo_and_kernel_io_split_the_glue():
    c = ctx_of()
    halo, kio = read("halo_ms", c), read("kernel_io_ms.short_jobs", c)
    assert halo == pytest.approx(10e-6)     # pad.1 + concatenate.2
    assert kio == pytest.approx(5e-6)       # pad.3; the kernel never counts
    glue = read("step_glue_ms", _with_summaries(c))
    assert halo + kio < glue == pytest.approx(20e-6)  # + convert.5


def _with_summaries(c):
    c.devices = [tr.summarize(d, c.lo, c.hi) for d in c.trace.devices]
    return c


def test_halo_mb_per_job_from_the_dispatch_arguments():
    assert read("halo_mb_per_job", ctx_of()) == pytest.approx(6.603776)


@pytest.mark.parametrize("metric", ["host_path_idle_ms", "step_host_ms",
                                    "halo_ms", "kernel_io_ms",
                                    "halo_mb_per_job"])
@pytest.mark.parametrize("case", ["no_spans", "no_jobs", "no_devices",
                                  "no_scopes", "other_module"])
def test_readers_return_none_where_nothing_is_there(metric, case):
    kw = {"no_spans": dict(host=[]), "no_jobs": dict(jobs=0),
          "no_devices": dict(devices=[]), "no_scopes": dict(scopes={}),
          "other_module": dict(module="jit_other")}[case]
    value = read(metric, ctx_of(**kw))
    silent = {"no_spans": {"host_path_idle_ms", "step_host_ms",
                           "halo_mb_per_job"},
              "no_jobs": {"host_path_idle_ms", "step_host_ms", "halo_ms",
                          "kernel_io_ms", "halo_mb_per_job"},
              "no_devices": {"host_path_idle_ms", "halo_ms",
                             "kernel_io_ms"},
              "no_scopes": {"halo_ms", "kernel_io_ms"},
              "other_module": {"halo_ms", "kernel_io_ms"}}[case]
    assert (value is None) == (metric in silent), value


def test_spans_outside_the_window_are_left_out():
    c = ctx_of()
    c.program.spans.append(H("pctpu.step.dispatch", 95, 120,
                             halo_bytes=10**9))
    assert read("halo_mb_per_job", c) == pytest.approx(6.603776)


def test_idle_named_by_innermost_program_span():
    got = dict(pt.idle_by_span(ctx_of()))
    assert got == {"pctpu.run_planar (1 gaps)": pytest.approx(10e-9),
                   "pctpu.step.dispatch (1 gaps)": pytest.approx(10e-9)}


def test_scope_map_needs_the_program_method(monkeypatch):
    from parallel_convolution_tpu.models import ConvolutionModel

    monkeypatch.delattr(ConvolutionModel, "iterate_text")
    cell = run.cell_of(run.load_spec(), "edge5_grey_2k.iterate")
    assert pt.build_scopes(cell, 4) == ({}, "")


def test_load_spans_finds_none_in_an_empty_dir(tmp_path):
    assert pt.load_spans(str(tmp_path)) == []


# What each existing metric reads on the fixture at the parent commit: this
# PR adds readers and changes none.
PARENT = {
    "blur3_rgb_16k.iterate": {
        "step_glue_ms": 5.322338, "kernel_ms": 48.39767,
        "stencil_roofline": 1.2669565240712037,
        "device_idle_share": 0.025812393440127757},
    "edge5_grey_2k.iterate": {
        "step_glue_ms.short_jobs": 5.322338,
        "kernel_ms.short_jobs": 48.39767,
        "stencil_roofline.short_jobs": 3.5193236779755668,
        "device_idle_share.short_jobs": 0.025812393440127757},
    "edge5_grey_2k.mesh2x2": {
        "step_glue_ms": 5.322338, "kernel_ms": 48.39767,
        "stencil_roofline": 0.8798309194938917,
        "device_idle_share": 0.025812393440127757},
}


@pytest.fixture(scope="module")
def fixture_trace():
    return tr.load(FIXTURE)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_existing_metrics_read_as_on_the_parent(fixture_trace, name):
    (body,) = [m for m in fixture_trace.devices[0].modules
               if tr.module_name(m) == "jit_body"]
    cell = run.cell_of(run.load_spec(), name)
    c = run.Context(cell=cell, jobs=1, window_s=body.dur / 1e9,
                    setup_s=0.0, pixels_per_job=8192 * 8192,
                    peak=work.peak_for("TPU v5 lite"), trace=fixture_trace,
                    lo=body.start, hi=body.end,
                    devices=[tr.summarize(d, body.start, body.end)
                             for d in fixture_trace.devices])
    # The fixture predates the program's spans and scopes: the new
    # readers find nothing there and are left out.
    c.program = pt.ProgramTrace(pt.load_spans(FIXTURE), scopes={})
    got = {k: v["value"] for k, v in run.read_metrics(cell.per_layer,
                                                      c).items()}
    assert got == PARENT[name]
