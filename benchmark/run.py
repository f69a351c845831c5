"""Run one benchmark cell on the chips of this machine and print its result.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic file (``benchmark/traffic/<traffic>.json``)
and one reader per metric (``benchmark/metrics/<metric>.py``).  The window
drives ``ConvolutionModel.run_planar`` (``closed_loop.py``); after it closes,
the plain reference (``reference.py``) recomputes the checked job on the
cell's own mesh, in the resident image's sharding, and every byte is
compared there.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.  A machine without
the accelerator, or with fewer chips than the cell asks for, exits 2 and
prints no result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # set-up is measured from process start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ACCELERATOR = "tpu"  # the only platform a run may report; tests steer it
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
LIMITS = {"mismatched_bytes": 0, "max_abs_diff": 0}  # exact comparison
TRAFFIC_KEYS = {"mesh", "about"}  # what the closed loop reads; no other


class NoAccelerator(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Context:
    """What a metric reader reads: the window and, traced, its reduction."""
    cell: Cell
    jobs: int
    window_s: float
    setup_s: float
    pixels_per_job: int     # C * H * W of the whole image
    peak: dict | None = None
    trace: object = None    # trace_reduce.Trace
    lo: int = 0             # the traced window on the trace's clock (ns)
    hi: int = 0
    devices: list = dataclasses.field(default_factory=list)  # summaries

    @property
    def iters(self) -> int:
        return int(self.cell.config["iters_per_job"])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_of(spec: dict, name: str, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     w["traffic"] + ".json"))
    if set(traffic) - TRAFFIC_KEYS:
        raise ValueError(f"traffic {w['traffic']!r}: keys "
                         f"{sorted(set(traffic) - TRAFFIC_KEYS)} are not "
                         f"read by the closed loop (it reads "
                         f"{sorted(TRAFFIC_KEYS)})")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, conf["file"])),
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)])


def metric_reader(name: str):
    """The reader module ``benchmark/metrics/<name>.py``, by file path."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(entries: list[dict], ctx: Context) -> dict:
    """Each metric its reader finds something for; the rest are left out."""
    out = {}
    for m in entries:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def image_shape(cell: Cell) -> tuple[int, int, int]:
    R, C = cell.traffic["mesh"]
    bh, bw = cell.config["block_hw"]
    return int(cell.config["channels"]), R * bh, C * bw


def devices_for(cell: Cell) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != ACCELERATOR:
        raise NoAccelerator(f"JAX found {devs[0].platform!r}, not "
                            f"{ACCELERATOR!r}")
    if len(devs) < cell.chips:
        raise NoAccelerator(f"{cell.name} needs {cell.chips} chips, JAX "
                            f"found {len(devs)}")
    R, C = cell.traffic["mesh"]
    if R * C != cell.chips:
        raise ValueError(f"{cell.name}: mesh {R}x{C} on {cell.chips} chips")
    return devs[:cell.chips]


def program_job(cell: Cell, devs):
    """The timed entry: ``run_planar(x, iters).astype(uint8)`` (unfenced)."""
    import jax
    import jax.numpy as jnp

    from parallel_convolution_tpu.models import ConvolutionModel
    from parallel_convolution_tpu.parallel.mesh import make_grid_mesh

    conf, plan = cell.config, cell.config["plan"]
    model = ConvolutionModel(
        filt=conf["filter"], mesh=make_grid_mesh(devs, tuple(
            cell.traffic["mesh"])),
        backend=plan["backend"], storage=plan["storage"], fuse=plan["fuse"],
        quantize=conf["quantize"], boundary=conf["boundary"],
        fallback=False)
    iters = int(conf["iters_per_job"])
    span = jax.profiler.TraceAnnotation

    def job(x):
        with span("bench.run_planar"):
            out = model.run_planar(x, iters)
        with span("bench.astype"):
            return out.astype(jnp.uint8)

    return job, model.mesh


def peak_bytes(devs) -> int:
    """The peak on the fullest chip, as the backend reports it."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def _configure_jax() -> None:
    import jax

    # One fixed directory inside the checkout: only a cell's first run
    # there compiles.  Small programs are cached too, so set-up is steady.
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No size limit, so no eviction: an evicting cache reads every entry's
    # access-time file before each write, and one entry without it (a
    # directory restored from elsewhere) made every write fail.
    jax.config.update("jax_compilation_cache_max_size", -1)


def check(win, image, cell: Cell) -> dict:
    """Recompute the checked job with the reference on the cell's own mesh,
    in the resident image's sharding; compare every byte there."""
    from benchmark import reference

    if win.kept is None:  # no job started in time to be checked
        C, H, W = image_shape(cell)
        return {"mismatched_bytes": C * H * W, "max_abs_diff": 255}
    want = reference.device_run(image, reference.taps_of(cell.config),
                                int(cell.config["iters_per_job"]),
                                cell.config["arithmetic"])
    return reference.compare(win.kept, want)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             job=None) -> dict:
    """One run of ``cell``: set-up, the window, the check; the result dict.

    ``job`` replaces the program's entry: the control and the fault tests
    put theirs in its place.
    """
    _configure_jax()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import closed_loop, trace_reduce, work

    devs = devices_for(cell)
    program, mesh = program_job(cell, devs)
    job = job or program
    shape = image_shape(cell)
    sharding = NamedSharding(mesh, PartitionSpec(None, *mesh.axis_names))
    image = closed_loop.device_image(shape, seed, sharding)
    closed_loop.warm_up(job, image)
    keep_at = closed_loop.check_fraction(seed)
    # Set-up's objects leave the collector's scans, so that a collection
    # in the window costs what the window's own garbage costs.
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - _T0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        win = closed_loop.run(job, image, seconds, keep_at)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = peak_bytes(devs)
    checks = check(win, image, cell)
    win.kept = image = None
    print(f"memory peak bytes: window {memory_peak}, with the check "
          f"{peak_bytes(devs)}", file=sys.stderr)
    ok = all(checks[k] <= LIMITS[k] for k in LIMITS)

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(memory_peak)}
    ctx = Context(cell=cell, jobs=win.jobs, window_s=win.seconds,
                  setup_s=setup_s,
                  pixels_per_job=shape[0] * shape[1] * shape[2])
    result = {"correct": ok, "attempted": win.jobs, "failed": 0 if ok else 1}
    if trace:
        if dev[0].platform == "tpu":
            ctx.peak = work.peak_for(dev[0].device_kind)
        ctx.trace = trace_reduce.load(TRACE_DIR)
        ctx.lo, ctx.hi = trace_reduce.window_of(ctx.trace)
        ctx.devices = [trace_reduce.summarize(d, ctx.lo, ctx.hi)
                       for d in ctx.trace.devices]
        result["metrics"] = read_metrics(cell.per_layer, ctx)
        busy = [d.busy for d in ctx.devices]
        device["busy_s"] = sum(busy) / max(1, len(busy)) / 1e9
        device["window_s"] = (ctx.hi - ctx.lo) / 1e9
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ctx.trace, ctx.lo, ctx.hi),
            "idle_gaps": trace_reduce.idle_by_host(ctx.trace, ctx.lo,
                                                   ctx.hi)}
    else:
        result["metrics"] = read_metrics(cell.end_to_end, ctx)
        result["device"] = device
    result["checked_job"] = win.kept_index
    result["job_s"] = {"min": min(win.job_s), "median": statistics.median(
        win.job_s), "max": max(win.job_s),
        "slowest": win.job_s.index(max(win.job_s))}
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    return result


def emit(result: dict) -> None:
    """Result line last on stdout; the compared numbers last on stderr."""
    print(json.dumps(result), flush=True)
    print(f"job seconds {result['job_s']}", file=sys.stderr)
    print(f"checked job {result['checked_job']} of {result['attempted']} "
          "in the window", file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = cell_of(load_spec(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
