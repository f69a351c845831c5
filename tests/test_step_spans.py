"""The job path's profiler spans, the halo counts the runner keeps, the
device scopes of the compiled executable, and what a span costs off.

Spans are read back from a ``jax.profiler`` trace of CPU devices (the
conftest's eight virtual devices), the way the benchmark reads them on
the chip.
"""

import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from parallel_convolution_tpu.models import ConvolutionModel
from parallel_convolution_tpu.obs import attribution, events, metrics, trace
from parallel_convolution_tpu.parallel import step
from parallel_convolution_tpu.parallel.mesh import make_grid_mesh
from parallel_convolution_tpu.utils.tracing import PhaseTimer

STEP_SPANS = ("pctpu.resolve", "pctpu.step.build", "pctpu.step.dispatch")
CASES = [((1, 1), "zero"), ((2, 2), "zero"), ((1, 4), "zero"),
         ((1, 1), "periodic"), ((2, 2), "periodic"), ((1, 4), "periodic")]
SHAPE, ITERS, FUSE = (1, 16, 24), 5, 2   # 2 fused rounds and a tail


@pytest.fixture(autouse=True)
def _obs_on_no_log():
    was = metrics.enabled()
    metrics.set_enabled(True)
    events.deconfigure()
    yield
    metrics.set_enabled(was)


def _model(grid, boundary, **kw):
    mesh = make_grid_mesh(jax.devices()[: grid[0] * grid[1]], grid)
    return ConvolutionModel("blur3", mesh=mesh, fuse=FUSE, boundary=boundary,
                            **kw)


def _host_spans(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name, dict(e.stats), line.name)
                        for e in line.events if e.name.startswith("pctpu.")]
    return sorted(out)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler trace: a warm run_planar per case, then a cold one
    (a shape not run before) and a nested PhaseTimer phase."""
    was = metrics.enabled()
    metrics.set_enabled(True)
    x = np.random.default_rng(3).integers(0, 256, SHAPE).astype(np.float32)
    models = [_model(g, b) for g, b in CASES]
    for m in models:
        m.run_planar(x, ITERS).block_until_ready()
    cold = np.zeros((1, 8, 24), np.float32)
    log_dir = str(tmp_path_factory.mktemp("prof"))
    jax.profiler.start_trace(log_dir)
    try:
        for m in models:
            m.run_planar(x, ITERS).block_until_ready()
        models[1].run_planar(cold, ITERS).block_until_ready()
        timer = PhaseTimer()
        with timer.phase("serve"), timer.phase("device"):
            pass
    finally:
        jax.profiler.stop_trace()
        metrics.set_enabled(was)
    spans = _host_spans(log_dir)
    roots = [s for s in spans if s[2] == "pctpu.run_planar"]
    per_call = [[s for s in spans if r[0] <= s[0] and s[1] <= r[1]
                 and s is not r] for r in roots]
    return roots, per_call, spans


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[f"{g[0]}x{g[1]}-{b}" for g, b in CASES])
def test_run_planar_nests_the_step_spans_with_traced_counts(traced, case):
    roots, per_call, _ = traced
    (grid, boundary), inner = CASES[case], per_call[case]
    assert [s[2] for s in inner] == list(STEP_SPANS)
    assert {s[4] for s in inner} == {roots[case][4]}   # one host thread
    (dispatch,) = [s for s in inner if s[2] == "pctpu.step.dispatch"]
    block = (SHAPE[1] // grid[0], SHAPE[2] // grid[1])
    want = attribution.halo_bytes_total(grid, block, 1, FUSE, ITERS, 1,
                                        "f32", boundary)
    assert dispatch[3]["halo_bytes"] == want["total"]
    # The formula counts a round per chunk even where no collective is
    # built; the traced count is 0 on a 1x1 grid.
    assert dispatch[3]["exchanges"] == (0 if grid == (1, 1)
                                        else want["rounds"])
    (build,) = [s for s in inner if s[2] == "pctpu.step.build"]
    assert "compiled" not in build[3]                   # warm: no compile


def test_cold_call_marks_its_build_span_compiled(traced):
    _, per_call, _ = traced
    (build,) = [s for s in per_call[len(CASES)]
                if s[2] == "pctpu.step.build"]
    assert build[3] == {"compiled": 1, "tap_form": "direct"}


def test_phase_timer_phases_reach_the_profiler(traced):
    names = [s[2] for s in traced[2] if s[2].startswith("pctpu.phase.")]
    assert names == ["pctpu.phase.serve", "pctpu.phase.serve/device"]


def test_iterate_prepared_counts_what_the_runner_built():
    """The counters carry the runner's traced counts: 0 on 1x1, where the
    cost-model formula would still count rounds."""
    metrics.reset()
    m = _model((1, 1), "zero")
    m.run_planar(np.ones(SHAPE, np.float32), ITERS)
    rounds = metrics.counter("pctpu_halo_rounds_total", "", ("backend",))
    assert rounds.value(backend="shifted") == 0
    assert metrics.counter("pctpu_iterations_total", "",
                           ("backend",)).value(backend="shifted") == ITERS


def test_rdma_counts_the_bands_it_moves_in_kernel():
    """pallas_rdma exchanges by remote DMA inside the kernel, with no
    ppermute: its runner counts the same ghost bands, on its block of
    whole (8, 128) f32 tiles."""
    metrics.reset()
    grid = (2, 2)
    _model(grid, "zero", backend="pallas_rdma").run_planar(
        np.ones(SHAPE, np.float32), ITERS).block_until_ready()
    block = step.block_extent(SHAPE[1:], grid, "pallas_rdma", "f32")
    assert block == (8, 128)
    want = attribution.halo_bytes_total(grid, block, 1, FUSE, ITERS, 1,
                                        "f32")
    hb = metrics.counter("pctpu_halo_bytes_total", "",
                         ("backend", "direction"))
    for d in attribution.DIRECTIONS:
        assert hb.value(backend="pallas_rdma", direction=d) == want[d]
    assert metrics.counter("pctpu_halo_rounds_total", "", ("backend",)
                           ).value(backend="pallas_rdma") == want["rounds"]


def _mono_wire(planes, h, w, d, itemsize=4):
    """One monolithic-kernel exchange on a 2x2 zero-boundary mesh: each
    of 2 row channels has 2 senders (one per mesh column) moving a d x w
    slab, each column channel 2 senders moving a (h + 2d) x d slab, all
    from the f32 VMEM pad."""
    return 2 * 2 * planes * itemsize * (d * w + (h + 2 * d) * d)


def test_rdma_spans_describe_the_launch_and_its_wire_bytes(tmp_path):
    """On the RDMA form the build span names the kernel, the column
    transport, overlap and the aligned block; the dispatch span carries
    the wire bytes of the plan's slabs beside the ghost bytes."""
    metrics.reset()
    grid, shape = (2, 2), (3, 16, 24)
    m = _model(grid, "zero", backend="pallas_rdma", storage="bf16")
    x = np.full(shape, 7, np.float32)
    m.run_planar(x, ITERS).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        m.run_planar(x, ITERS).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    spans = {s[2]: s[3] for s in _host_spans(str(tmp_path))}
    h, w = step.block_extent(shape[1:], grid, "pallas_rdma", "bf16")
    assert (h, w) == (16, 128)
    build = spans["pctpu.step.build"]
    assert build["rdma_kernel"] == "monolithic"
    assert build["col_mode"] == m.effective_col_mode
    assert build["overlap"] == 0
    assert build["block"] == str([h, w])
    dispatch = spans["pctpu.step.dispatch"]
    n_chunks, rem = divmod(ITERS, FUSE)
    want = (n_chunks * _mono_wire(3, h, w, FUSE)
            + _mono_wire(3, h, w, rem))
    assert dispatch["rdma_wire_bytes"] == want
    assert dispatch["halo_bytes"] == attribution.halo_bytes_total(
        grid, (h, w), 1, FUSE, ITERS, 3, "bf16")["total"]
    wire = metrics.counter("pctpu_rdma_wire_bytes_total", "", ("backend",))
    assert wire.value(backend="pallas_rdma") == 2 * want


def test_ppermute_dispatch_has_no_wire_bytes_and_the_same_block():
    """A form that exchanges by ppermute keeps the mesh's own block and
    reports no remote-DMA bytes."""
    metrics.reset()
    assert step.block_extent((16, 24), (2, 2), "pallas_sep", "bf16") == \
        (8, 12)
    _model((2, 2), "zero", backend="pallas_sep", storage="bf16").run_planar(
        np.ones(SHAPE, np.float32), ITERS).block_until_ready()
    wire = metrics.counter("pctpu_rdma_wire_bytes_total", "", ("backend",))
    assert wire.value(backend="pallas_sep") == 0


@pytest.mark.parametrize("storage,fuse", [("bf16", 16), ("u8", 32)])
def test_tiled_launch_wire_bytes_are_whole_bands(storage, fuse):
    """The tiled kernel moves whole (sub_v, 128) bands: rows sub_v deep
    across the block, columns 128 wide down the padded height."""
    from parallel_convolution_tpu.ops import pallas_rdma

    dtype = {"bf16": jax.numpy.bfloat16, "u8": jax.numpy.uint8}[storage]
    C, h, w = 3, 960, 1280
    launch = pallas_rdma.launch_plan((C, h, w), dtype, dtype, 1, fuse,
                                     (2, 2), "zero", "packed")
    sub_v, B = {"bf16": (16, 2), "u8": (32, 1)}[storage]
    assert launch.kernel == "tiled" and launch.sub_v == sub_v
    h_pad = -(-h // launch.th) * launch.th + 2 * sub_v
    assert launch.pad_hw[0] == h_pad
    rows = 2 * 2 * C * sub_v * w * B
    cols = 2 * 2 * C * h_pad * 128 * B
    assert launch.wire_bytes == rows + cols
    ghosts = attribution.halo_bytes_total((2, 2), (h, w), 1, fuse, fuse, C,
                                          storage)["total"]
    assert launch.wire_bytes >= ghosts


def test_op_scopes_map_the_halo_and_kernel_io_ops():
    m = _model((2, 2), "zero", backend="pallas_sep")
    scopes = step.op_scopes(m.iterate_text((1, 32, 48), ITERS))
    by = {}
    for name, scope in scopes.items():
        by.setdefault(scope, set()).add(name.split(".")[0])
    assert {"ppermute", "concatenate"} <= by["pctpu.halo"]
    assert "pad" in by["pctpu.kernel_io"]
    assert by[""]                                   # the rest: no scope


def test_op_scopes_give_a_fusion_its_roots_scope():
    text = "\n".join([
        "HloModule jit_body, entry_computation_layout={()->f32[]}",
        "",
        "%fused_computation (p: f32[4]) -> f32[8] {",
        '  %p = f32[4]{0} parameter(0)',
        '  ROOT %pad.2 = f32[8]{0} pad(%p, %c), padding=0_4, '
        'metadata={op_name="jit(body)/pctpu.kernel_io/jit(_pad)/pad"}',
        "}",
        "",
        "ENTRY %main.3 (a: f32[4]) -> f32[8] {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="a"}',
        '  %concatenate.1 = f32[4]{0} concatenate(%a), dimensions={0}, '
        'metadata={op_name="jit(body)/shard_map/pctpu.halo/concatenate"}',
        '  ROOT %concatenate_pad_fusion = f32[8]{0} fusion(%concatenate.1), '
        'kind=kLoop, calls=%fused_computation, '
        'metadata={op_name="jit(body)/pctpu.halo/concatenate"}',
        "}"])
    assert step.op_scopes(text) == {
        "p": "", "pad.2": "pctpu.kernel_io", "a": "",
        "concatenate.1": "pctpu.halo",
        "concatenate_pad_fusion": "pctpu.kernel_io"}


def test_span_off_path_cost_and_disabled_no_op():
    """No profiler and no event log: a span is the profiler event alone,
    with no ids; PCTPU_OBS=0 keeps the shared no-op span."""
    with trace.span("pctpu.step.dispatch") as sp:
        assert sp.context is None and trace.current() is None
        sp.set(exchanges=1)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("pctpu.step.build"):
            pass
    assert (time.perf_counter() - t0) / n < 5e-6
    # A caller that asks for a trace gets ids even without a log.
    with trace.span("request", parent=None) as sp:
        assert sp.context is not None and trace.current() == sp.context
    metrics.set_enabled(False)
    assert trace.span("pctpu.run_planar") is trace.NULL_SPAN
    assert trace.annotate("pctpu.phase.x") is trace.NULL_SPAN
