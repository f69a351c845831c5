"""One resolver for every entry point (``step.resolve_launch``).

The agreement table: each entry point resolves a request to the same
``step.Launch`` that ``resolve_launch`` gives for it — backend, fuse,
tile, overlap, column transport, storage, boundary, the padded block and
the plan's provenance.  Each entry is stopped right after it resolved, at
the seam where it hands the launch on, so the table compiles nothing but
the degrade probes of the fallback request.
"""

import jax
import numpy as np
import pytest

from parallel_convolution_tpu.models import ConvolutionModel
from parallel_convolution_tpu.ops import filters
from parallel_convolution_tpu.parallel import kernels as kernel_forms
from parallel_convolution_tpu.parallel import mesh as mesh_lib, step
from parallel_convolution_tpu.resilience import degrade, faults
from parallel_convolution_tpu.serving.engine import WarmEngine
from parallel_convolution_tpu.solvers import multigrid

FILT = filters.get_filter("blur3")
SHAPE = (1, 32, 256)
CHECK_EVERY = 4

# name -> (mesh grid, image shape, knobs).  Float mode and f32 carries
# throughout, so the multigrid solver can take every request.
REQUESTS = {
    "explicit_pallas_sep": ((1, 1), SHAPE, dict(backend="pallas_sep")),
    "auto_plan_file": ((1, 1), SHAPE, dict(backend="auto", fuse=None)),
    "rdma_2x2_col_auto": ((2, 2), SHAPE,
                          dict(backend="pallas_rdma", col_mode=None)),
    "overlap_off_rdma": ((1, 1), SHAPE,
                         dict(backend="pallas", overlap=True)),
    # Its own shape: the planted compile failure must meet a cold runner.
    "fallback_degrades": ((1, 1), (1, 24, 256),
                          dict(backend="pallas_sep", fallback=True)),
}


class _Resolved(Exception):
    """Raised at an entry point's hand-off seam, carrying its launch."""

    def __init__(self, launch):
        super().__init__("resolved")
        self.launch = launch


def _mesh(grid):
    return mesh_lib.make_grid_mesh(jax.devices()[: grid[0] * grid[1]], grid)


def _knobs(knobs):
    full = dict(backend="shifted", fuse=1, tile=None, overlap=None,
                col_mode=None, fallback=False)
    full.update(knobs)
    return full


def _stop(monkeypatch, owner, name, pick):
    """Replace ``owner.name`` by a seam that raises the launch ``pick``
    finds in its arguments."""
    def seam(*args, **kw):
        raise _Resolved(pick(*args, **kw))
    monkeypatch.setattr(owner, name, seam)


def _model_run(mesh, shape, kn, monkeypatch):
    model = ConvolutionModel(filt=FILT, mesh=mesh, quantize=False, **kn)
    _stop(monkeypatch, step, "_run_launch", lambda *a: a[4])
    model.run_planar(np.zeros(shape, np.float32), 2)


def _model_iterate_text(mesh, shape, kn, monkeypatch):
    model = ConvolutionModel(filt=FILT, mesh=mesh, quantize=False, **kn)
    _stop(monkeypatch, step, "_launch_text", lambda *a: a[4])
    model.iterate_text(shape, 2)


def _sharded_iterate(mesh, shape, kn, monkeypatch):
    _stop(monkeypatch, step, "_run_launch", lambda *a: a[4])
    step.sharded_iterate(np.zeros(shape, np.float32), FILT, 2, mesh=mesh,
                         quantize=False, **kn)


def _iterate_text(mesh, shape, kn, monkeypatch):
    _stop(monkeypatch, step, "_launch_text", lambda *a: a[4])
    step.iterate_text(shape, FILT, 2, mesh=mesh, quantize=False, **kn)


def _iterate_prepared(mesh, shape, kn, monkeypatch):
    # The caller's own array states the block: prepare it as a plain
    # resolution would, then hand it in.
    ref = step.resolve_launch(mesh, FILT, shape, quantize=False,
                              **{**kn, "fallback": False})
    xs, valid_hw, _ = step._prepare(np.zeros(shape, np.float32), mesh, ref)
    _stop(monkeypatch, step, "_iterate_launch", lambda *a: a[5])
    step.iterate_prepared(xs, FILT, 2, mesh, valid_hw, quantize=False, **kn)


def _converge_seam(monkeypatch):
    setup = step._converge_setup

    def seam(*args, **kw):
        raise _Resolved(setup(*args, **kw)[0])
    monkeypatch.setattr(step, "_converge_setup", seam)


def _sharded_converge(mesh, shape, kn, monkeypatch):
    _converge_seam(monkeypatch)
    step.sharded_converge(np.zeros(shape, np.float32), FILT, 0.1, 8,
                          check_every=CHECK_EVERY, mesh=mesh, **kn)


def _sharded_converge_stream(mesh, shape, kn, monkeypatch):
    _converge_seam(monkeypatch)
    next(step.sharded_converge_stream(
        np.zeros(shape, np.float32), FILT, 0.1, 8,
        check_every=CHECK_EVERY, mesh=mesh, **kn))


def _multigrid(mesh, shape, kn, monkeypatch):
    _stop(monkeypatch, multigrid, "_mg_cycles", lambda *a: a[5])
    multigrid.mg_converge(np.zeros(shape, np.float32), FILT, 0.1, 8,
                          mesh=mesh, **kn)


def _engine(mesh, shape, kn, monkeypatch):
    eng = WarmEngine(mesh, fallback=kn.pop("fallback"))
    monkeypatch.setattr(WarmEngine, "_compile_batch",
                        lambda self, entry, batch: None)
    key, _ = eng.resolve_key(shape, filter_name=FILT.name, iters=2,
                             quantize=False, **kn)
    raise _Resolved(eng.entry(key).launch)


ENTRIES = {
    "model_run": _model_run,
    "model_iterate_text": _model_iterate_text,
    "sharded_iterate": _sharded_iterate,
    "iterate_text": _iterate_text,
    "iterate_prepared": _iterate_prepared,
    "sharded_converge": _sharded_converge,
    "sharded_converge_stream": _sharded_converge_stream,
    "multigrid": _multigrid,
    "engine": _engine,
}


@pytest.fixture
def plan_file(tmp_path, monkeypatch):
    """An armed plan file holding one measured plan for the fixed-count
    workload and the same plan for its convergence twin."""
    from parallel_convolution_tpu.tuning.plans import (
        PLAN_FILE_ENV, Plan, PlanCache, Workload,
    )

    cache = PlanCache(str(tmp_path / "plans.json"))
    plan = Plan(backend="pallas", fuse=1, tile=None, source="measured")
    for check_every in (None, CHECK_EVERY):
        cache.put(Workload.from_mesh(_mesh((1, 1)), FILT, SHAPE,
                                     quantize=False,
                                     check_every=check_every), plan)
    monkeypatch.setenv(PLAN_FILE_ENV, cache.save())


@pytest.fixture
def fresh_probes():
    """The degrade walk's verdicts are process-wide: start and leave
    every case with none."""
    degrade.clear_probe_cache()
    yield
    degrade.clear_probe_cache()


@pytest.mark.parametrize("request_name", list(REQUESTS))
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_entry_points_agree_on_the_launch(entry, request_name, plan_file,
                                          fresh_probes, monkeypatch):
    grid, shape, knobs = REQUESTS[request_name]
    kn = _knobs(knobs)
    mesh = _mesh(grid)
    with faults.injected("backend_compile:1" if kn["fallback"] else None):
        with pytest.raises(_Resolved) as got:
            ENTRIES[entry](mesh, shape, dict(kn), monkeypatch)
    # The probe verdicts the entry left are what the reference reads, so
    # both see the same walk.
    want = step.resolve_launch(mesh, FILT, shape, quantize=False, **kn)
    assert got.value.launch == want
    if request_name == "auto_plan_file":
        assert (want.backend, want.plan_source) == ("pallas", "measured")
    if request_name == "rdma_2x2_col_auto":
        assert want.block_hw == step.block_extent(
            shape[1:], (2, 2), "pallas_rdma", "f32", "zero")
    if request_name == "overlap_off_rdma":
        assert want.overlap is False
    if request_name == "fallback_degrades":
        assert want.backend == "pallas"


def test_serving_block_is_the_tile_aligned_rdma_block(monkeypatch):
    # The reference's 1920x2520 RGB image on 2x2: the engine pads each
    # 960x1260 block to whole (32, 128) u8 tiles, as run_planar does.
    eng = WarmEngine(_mesh((2, 2)))
    key, _ = eng.resolve_key((3, 1920, 2520), backend="pallas_rdma",
                             storage="u8", fuse=16, iters=100)

    def seam(x, mesh, launch):
        raise _Resolved(launch)
    monkeypatch.setattr(step, "_prepare", seam)
    with pytest.raises(_Resolved) as got:
        eng.entry(key)
    assert got.value.launch.block_hw == (960, 1280)


def test_run_planar_resolves_once(monkeypatch):
    calls = []
    resolve_overlap = step.resolve_overlap

    def counting(*args, **kw):
        calls.append(args)
        return resolve_overlap(*args, **kw)
    monkeypatch.setattr(step, "resolve_overlap", counting)
    model = ConvolutionModel(filt=FILT, mesh=_mesh((2, 2)))
    model.run_planar(np.zeros((1, 20, 36), np.float32), 2)
    assert len(calls) == 1


@pytest.mark.parametrize("entry", ["model_run", "multigrid", "engine"])
def test_overlap_reclamped_by_the_registry_rule_alone(entry, fresh_probes,
                                                      monkeypatch):
    # A stand-in rule the registry's clamp returns: every entry's
    # re-clamped overlap after the degrade walk must be exactly its word.
    monkeypatch.setattr(kernel_forms, "clamp_overlap",
                        lambda overlap, name, rank=2: "registry-rule")
    kn = _knobs(dict(backend="shifted", fallback=True))
    with pytest.raises(_Resolved) as got:
        ENTRIES[entry](_mesh((1, 1)), SHAPE, kn, monkeypatch)
    assert got.value.launch.overlap == "registry-rule"
