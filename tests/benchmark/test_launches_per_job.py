"""The reader of device program launches per job, on made-up traces and
on the recorded v5e fixture."""

import os

import pytest

from benchmark import program_trace as pt, run, trace_reduce as tr

FIXTURE = os.path.join(run.BENCH, "fixtures", "flagship_8192_fuse32.xplane.pb")


def S(name, a, b):
    return tr.Span(name, a, b)


def device(name, modules):
    return tr.Device(name, [S(m, a, b) for m, a, b in modules], [], [])


# Two jobs in the window [0, 100): each a job program and the
# benchmark's cast.  The first job's launch reads as starting before the
# window (the device clock a little behind the host's); launches wholly
# outside the window are not the window's.
JOBS = [("jit_job(3)", -60, -20),
        ("jit_job(3)", -3, 30), ("jit_convert_element_type(4)", 30, 35),
        ("jit_job(3)", 50, 80), ("jit_convert_element_type(4)", 80, 85),
        ("jit_job(3)", 120, 150)]
CALLS = [pt.HostSpan("pctpu.run_planar", -70, -50, {}),
         pt.HostSpan("pctpu.run_planar", 0, 20, {}),
         pt.HostSpan("pctpu.run_planar", 50, 70, {}),
         pt.HostSpan("pctpu.run_planar", 110, 125, {})]


def ctx_of(devices, host=CALLS, cell="rgb_2520.mesh2x2"):
    c = run.Context(cell=run.cell_of(run.load_spec(), cell), jobs=2,
                    window_s=1e-7, setup_s=0.0, pixels_per_job=1,
                    trace=tr.Trace(devices, []), lo=0, hi=100)
    c.program = pt.ProgramTrace(list(host), {}, "jit_job")
    return c


def read(name, ctx):
    return run.metric_reader(name).read(ctx)


def test_launches_per_job_counts_module_events_per_call():
    one = ctx_of([device("/device:TPU:0", JOBS)])
    assert read("launches_per_job", one) == pytest.approx(2.0)


def test_launches_per_job_is_averaged_over_the_chips():
    # The second chip also ran an eager pad and a gather in each job.
    extra = JOBS + [("jit__pad(5)", 36, 40), ("jit_gather(6)", 86, 90),
                    ("jit__pad(5)", 40, 44)]
    two = ctx_of([device("/device:TPU:0", JOBS),
                  device("/device:TPU:1", extra)],
                 cell="edge5_grey_2k.iterate")
    assert read("launches_per_job.short_jobs", two) == pytest.approx(
        (4 + 7) / 2 / 2)


@pytest.mark.parametrize("case", ["no_devices", "no_calls"])
def test_launches_per_job_is_none_where_nothing_is_there(case):
    c = ctx_of([] if case == "no_devices"
               else [device("/device:TPU:0", JOBS)],
               host=CALLS if case == "no_devices" else [])
    assert read("launches_per_job", c) is None


def test_the_fixture_predates_the_program_spans():
    """The recorded trace has launches but no ``pctpu.run_planar`` span:
    the reader finds no job to count them over."""
    trace = tr.load(FIXTURE)
    assert any(d.modules for d in trace.devices)
    (body,) = [m for m in trace.devices[0].modules
               if tr.module_name(m) == "jit_body"]
    c = run.Context(cell=run.cell_of(run.load_spec(),
                                     "blur3_rgb_16k.iterate"),
                    jobs=1, window_s=body.dur / 1e9, setup_s=0.0,
                    pixels_per_job=1, trace=trace, lo=body.start,
                    hi=body.end)
    c.program = pt.ProgramTrace(pt.load_spans(FIXTURE), scopes={})
    assert read("launches_per_job", c) is None


@pytest.mark.parametrize("name", ["blur3_rgb_16k.iterate",
                                  "edge5_grey_2k.iterate",
                                  "edge5_grey_2k.mesh2x2",
                                  "rgb_2520.mesh2x2"])
def test_every_cell_reports_launches_per_job(name):
    cell = run.cell_of(run.load_spec(), name)
    rate = cell.end_to_end[0]["name"]
    group = rate[len("gpx_per_s_per_chip"):]
    (metric,) = [m for m in cell.per_layer
                 if m["name"] == f"launches_per_job{group}"]
    assert metric["moves"] == rate
    assert metric["layer"] == "entry (models/pipeline.py)"
