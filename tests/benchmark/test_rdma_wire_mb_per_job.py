"""The ``rgb_2520.mesh2x2`` cell as the harness loads it, and the reader
of the remote-DMA wire bytes on made-up spans."""

import pytest

from benchmark import program_trace as pt, run, trace_reduce as tr

CELL = "rgb_2520.mesh2x2"


def ctx_of(host, jobs=2, cell=CELL):
    c = run.Context(cell=run.cell_of(run.load_spec(), cell), jobs=jobs,
                    window_s=1.0, setup_s=0.0, pixels_per_job=1,
                    trace=tr.Trace([], []), lo=0, hi=100)
    c.program = pt.ProgramTrace(list(host), {}, "jit_body")
    return c


def dispatch(a, b, **args):
    return pt.HostSpan("pctpu.step.dispatch", a, b, args)


def read(ctx):
    return run.metric_reader("rdma_wire_mb_per_job").read(ctx)


def test_wire_mb_per_job_sums_the_dispatch_spans_in_the_window():
    host = [dispatch(10, 20, exchanges=7, halo_bytes=5, rdma_wire_bytes=3e6),
            dispatch(50, 60, exchanges=7, halo_bytes=5, rdma_wire_bytes=3e6),
            dispatch(95, 120, rdma_wire_bytes=9e9)]       # ends after hi
    assert read(ctx_of(host)) == pytest.approx(3.0)


@pytest.mark.parametrize("host,jobs", [
    ([dispatch(10, 20, exchanges=13, halo_bytes=6603776)], 2),  # ppermute
    ([], 2),
    ([dispatch(10, 20, rdma_wire_bytes=1)], 0)])
def test_wire_mb_per_job_is_none_without_rdma_spans(host, jobs):
    """A program without the argument (a ppermute form, or an older
    commit) leaves the metric out."""
    assert read(ctx_of(host, jobs)) is None


def test_the_cell_loads_by_name():
    spec = run.load_spec()
    cell = run.cell_of(spec, CELL)
    assert cell.chips == 4 and cell.traffic["mesh"] == [2, 2]
    assert run.image_shape(cell) == (3, 1920, 2520)
    assert cell.config["plan"]["backend"] == "pallas_rdma"
    assert cell.config["reduced"] == []
    rate = [m["name"] for m in cell.end_to_end]
    assert rate[-1] == "setup_s" and len(rate) == 2
    per_layer = {m["name"] for m in cell.per_layer}
    group = rate[0][len("gpx_per_s_per_chip"):]
    assert {m["moves"] for m in cell.per_layer} == {rate[0]}
    assert {f"host_call_ms{group}", f"kernel_ms{group}",
            f"stencil_roofline{group}", f"halo_mb_per_job{group}",
            "rdma_wire_mb_per_job"} <= per_layer
    # No ppermute halo in this form: its scope and collectives are absent.
    assert not any(n.startswith(("halo_ms", "exchange_exposed_ms"))
                   for n in per_layer)
