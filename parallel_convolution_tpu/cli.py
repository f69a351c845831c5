"""Command-line entrypoint (reference component C12, SURVEY.md §2).

Mirrors the reference's argv vocabulary — ``image path, rows, cols, loops,
grey|rgb`` — and replaces its ad-hoc workflow (qsub scripts + manual ``cmp``
of raw outputs) with subcommands:

  run       filter a raw image on the TPU mesh (the parallel main())
  serial    same via the NumPy oracle (the serial main(); golden path)
  generate  create a deterministic test image (the bundled-waterfall analog)
  compare   byte-compare two raw images (the reference's validation step)
  convert   raw -> PGM/PPM for visual inspection
  bench     time a synthetic workload, print one JSON row (MPI_Wtime tier)
  info      devices / mesh / filters at a glance
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def _add_image_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("image", help="input .raw image path")
    p.add_argument("rows", type=int)
    p.add_argument("cols", type=int)
    p.add_argument("loops", type=int)
    p.add_argument("mode", choices=["grey", "rgb"])


def _add_perf_args(p: argparse.ArgumentParser) -> None:
    """Filter/mesh/kernel knobs shared by the run and bench subcommands."""
    # Choices come from the canonical jax-free registries so a new backend
    # or storage tier lands in the CLI without a second edit.
    from parallel_convolution_tpu.utils.config import (
        BACKEND_CHOICES, STORAGES,
    )

    p.add_argument("--filter", default="blur3", dest="filter_name")
    p.add_argument("--mesh", default=None,
                   help="RxC grid, e.g. 2x4 (default: $PCTPU_MESH if set "
                        "— the supervisor's reshape env — else all "
                        "devices).  A --checkpoint resume accepts a "
                        "DIFFERENT grid than the one that wrote the "
                        "snapshot: shards reshard transparently, bytes "
                        "are unchanged (elastic recovery)")
    p.add_argument("--backend", default=None, choices=list(BACKEND_CHOICES),
                   help="correlate implementation (default: shifted, the "
                        "normative XLA path).  'auto' resolves backend — "
                        "and any of --fuse/--tile left unset — through "
                        "the tuning subsystem: plan cache "
                        "(PCTPU_PLAN_FILE / scripts/tune.py --emit-plans) "
                        "when present, else the roofline cost model; "
                        "bits are identical to naming the resolved "
                        "backend explicitly")
    p.add_argument("--storage", default=None, choices=list(STORAGES),
                   help="iteration-carry dtype (default: f32); narrower "
                        "carries shrink HBM/ICI traffic and stay "
                        "bit-exact for u8 images")
    p.add_argument("--fuse", type=int, default=None, metavar="T",
                   help="iterations per halo exchange (temporal fusion; "
                        "default 1).  All backends, pallas_rdma included: "
                        "there the T*r-deep exchange AND the T levels run "
                        "inside one kernel (needs blocks >= radius*T)")
    p.add_argument("--tile", default=None, metavar="TH,TW",
                   help="Pallas kernel output-tile override, e.g. "
                        "1024,512 (default: per-kernel tuned value; "
                        "results are bit-identical for any tile)")
    p.add_argument("--overlap", default="auto",
                   choices=["auto", "on", "off"],
                   help="interior-first overlapped halo pipeline in the "
                        "RDMA kernels: ghost-band DMAs fly while the "
                        "block interior computes, receive waits retire "
                        "just before the rim (bit-identical to the "
                        "serialized order).  'auto' = off for explicit "
                        "backends, cost-model-decided for --backend "
                        "auto; 'on' is a request clamped to legality "
                        "(RDMA tier, compiled Pallas) — the RESOLVED "
                        "knob is what rows and summaries report")
    p.add_argument("--col-mode", default="auto", dest="col_mode",
                   choices=["auto", "packed", "strided"],
                   help="RDMA column-slab transport (round 16): "
                        "'packed' stages the strided slab through a "
                        "contiguous buffer and moves ONE dense RDMA, "
                        "'strided' issues the direct strided copy — "
                        "bit-identical either way; 'auto' lets the "
                        "cost model pick per (dtype, block, radius).  "
                        "Inert (normalized to 'packed') off the RDMA "
                        "tier; rows and responses stamp the RESOLVED "
                        "value")
    p.add_argument("--fallback", action="store_true",
                   help="graceful backend degradation: probe the backend "
                        "once and walk pallas_rdma -> pallas -> shifted "
                        "on a transient compile/launch failure instead of "
                        "dying (the effective backend is printed/stamped)")
    p.add_argument("--fast", action="store_true",
                   help="on a TPU, fill any knob NOT explicitly passed "
                        "with the measured flagship family "
                        "(pallas_sep/bf16/fuse 32; fuse "
                        "clamped to the per-device block).  Off-TPU the "
                        "compiled XLA path is already the fast one, so "
                        "unset knobs keep their normal defaults.  "
                        "Explicit flags always win; output bits are "
                        "identical either way.  The resolved knobs are "
                        "printed — pass them explicitly when resuming a "
                        "checkpoint on different hardware")


def _resolve_perf_knobs(args, mesh) -> None:
    """Fill backend/storage/fuse (argparse default None = not passed).

    --fast on a TPU resolves unset knobs to the measured flagship family
    (pallas_sep / bf16 / fuse 32, with fuse clamped so
    blocks stay >= radius*fuse) and prints the resolution — checkpoint
    resume keys on these values, so a resume on different hardware needs
    them passed explicitly.  Explicit flags always win (None-sentinel,
    not value comparison: an explicit `--fuse 1` stays unfused).  Off-TPU
    the Pallas kernels run under the interpreter — far slower than
    compiled XLA — so --fast leaves unset knobs at the normal defaults.
    All combinations are bit-identical; knobs change speed, never bytes.

    Touches jax.devices (through on_tpu), so it runs after parsing.
    """
    from parallel_convolution_tpu.utils.platform import on_tpu

    if getattr(args, "fast", False) and on_tpu():
        from parallel_convolution_tpu.ops.filters import get_filter
        from parallel_convolution_tpu.parallel.mesh import grid_shape

        if args.backend is None:
            args.backend = "pallas_sep"
        if args.storage is None:
            # Multigrid carries signed float residual/correction fields
            # (mg_converge rejects anything but f32) — --fast only
            # upgrades storage for the plain iterate/jacobi paths.
            args.storage = ("f32" if getattr(args, "solver", "jacobi")
                            == "multigrid" else "bf16")
        if args.fuse is None:
            R, C = grid_shape(mesh)
            block = min(-(-args.rows // R), -(-args.cols // C))
            r = get_filter(args.filter_name).radius
            args.fuse = max(1, min(32, block // max(1, r)))
        print(f"# --fast resolved: backend={args.backend} "
              f"storage={args.storage} fuse={args.fuse}", file=sys.stderr)
    if args.backend is None:
        args.backend = "shifted"
    if args.storage is None:
        args.storage = "f32"
    if args.fuse is None and args.backend != "auto":
        # backend='auto' keeps the None: it means 'tune the depth too'
        # (resolved with the backend through the plan cache/cost model).
        args.fuse = 1
    # --overlap: 'auto' -> None (off for explicit backends, tuned for
    # backend='auto'); on/off -> a clamped request (resolve_overlap).
    args.overlap = {"auto": None, "on": True, "off": False}[
        getattr(args, "overlap", "auto")]
    # --col-mode: 'auto' -> None (cost-model pick; resolve_col_mode).
    cm = getattr(args, "col_mode", "auto")
    args.col_mode = None if cm == "auto" else cm


def _run_volume(args, mesh) -> int:
    """The ``--rank 3`` arm of ``run``: the input file is raw float32
    ``(2, D, rows, cols)`` bytes (two interleaved fields — u/v for
    Gray–Scott, u/u_prev for wave, field+rhs for the FD forms), the
    output the same layout after ``loops`` sweeps (or a ``--converge``
    run).  Volumes stay float end-to-end — no u8 quantization."""
    from parallel_convolution_tpu.utils.config import (
        VOLUME_FIELDS, VOLUME_SMOOTH_FORMS, VOLUME_PHYSICS_FORMS,
    )
    from parallel_convolution_tpu.volumes import driver

    if args.depth is None or args.depth < 1:
        print("--rank 3 requires --depth D (the resident volume depth)",
              file=sys.stderr)
        return 2
    known = VOLUME_SMOOTH_FORMS + VOLUME_PHYSICS_FORMS
    if args.filter_name not in known:
        print(f"--rank 3 --filter must name a rank-3 form "
              f"({', '.join(known)}), got {args.filter_name!r}",
              file=sys.stderr)
        return 2
    if args.solver != "jacobi":
        print(f"--rank 3 supports --solver jacobi only (got "
              f"{args.solver}): rank-3 multigrid transfer ships as "
              "registry forms, not a CLI solver", file=sys.stderr)
        return 2
    want = (VOLUME_FIELDS, args.depth, args.rows, args.cols)
    raw = np.fromfile(args.image, dtype=np.float32)
    if raw.size != int(np.prod(want)):
        print(f"{args.image}: {raw.size} f32 values, expected "
              f"{int(np.prod(want))} for {want}", file=sys.stderr)
        return 2
    vol = raw.reshape(want)
    fuse = max(1, args.fuse or 1)
    r, c = mesh.shape["x"], mesh.shape["y"]
    if args.converge is not None:
        out, iters, diff = driver.volume_converge(
            vol, args.filter_name, tol=args.converge,
            max_iters=args.loops, check_every=args.check_every,
            mesh=mesh, boundary=args.boundary, fuse=fuse)
        np.ascontiguousarray(out, dtype=np.float32).tofile(args.output)
        print(f"volume converged after {iters} iters (diff {diff:.3g}, "
              f"tol {args.converge}) on {r}x{c} mesh -> {args.output}")
        return 0
    out = driver.volume_iterate(vol, args.filter_name, args.loops,
                                mesh=mesh, boundary=args.boundary,
                                fuse=fuse)
    np.ascontiguousarray(out, dtype=np.float32).tofile(args.output)
    print(f"ran {args.loops} x {args.filter_name} on "
          f"{args.depth}x{args.rows}x{args.cols} volume, {r}x{c} mesh "
          f"-> {args.output}")
    return 0


def _mesh_from_flag(spec: str | None):
    from parallel_convolution_tpu.parallel.mesh import mesh_from_spec

    # An unset --mesh falls back to the supervisor's reshape env
    # (PCTPU_MESH) inside mesh_from_spec — every entry point that routes
    # here inherits elastic re-gridding for free.
    return mesh_from_spec(spec)


def main(argv: list[str] | None = None) -> int:
    from parallel_convolution_tpu.resilience import diskio, faults
    from parallel_convolution_tpu.utils.config import BOUNDARIES, SOLVERS

    # Honor PCTPU_FAULTS / PCTPU_DISK_MODES so injected-fault drills run
    # end-to-end through the real CLI (no-op unless the env vars are set).
    faults.install_from_env()
    diskio.install_from_env()
    ap = argparse.ArgumentParser(prog="pconv-tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="distributed filtering on the TPU mesh")
    _add_image_args(run)
    run.add_argument("-o", "--output", required=True)
    _add_perf_args(run)
    run.add_argument("--boundary", default="zero",
                     choices=list(BOUNDARIES),
                     help="edge handling: zero ghost ring (the reference) "
                          "or periodic torus wrap")
    run.add_argument("--rank", type=int, default=2, choices=[2, 3],
                     help="workload rank: 2 = u8 images (the default), "
                          "3 = (2, D, rows, cols) raw float32 volumes "
                          "(two interleaved fields) through the rank-3 "
                          "registry forms — fd7/fd25 FD Laplacians, "
                          "wave leapfrog, Gray-Scott reaction-diffusion")
    run.add_argument("--depth", type=int, default=None, metavar="D",
                     help="volume depth (required with --rank 3): the "
                          "resident D axis; rows/cols shard on the mesh")
    run.add_argument("--converge", type=float, default=None, metavar="TOL",
                     help="run to convergence (loops becomes max iters)")
    run.add_argument("--solver", default="jacobi", choices=list(SOLVERS),
                     help="convergence strategy (with --converge): plain "
                          "jacobi sweeps, or the geometric multigrid "
                          "V-cycle (same stopping measure, orders of "
                          "magnitude fewer fine-grid work units)")
    run.add_argument("--mg-levels", type=int, default=None, metavar="N",
                     help="multigrid level-count cap (default: coarsen "
                          "to the planner's floor)")
    run.add_argument("--check-every", type=int, default=10)
    run.add_argument("--sharded-io", action="store_true",
                     help="block-stream the image between disk and devices "
                          "(huge images; never materializes on one host)")
    run.add_argument("--checkpoint", default=None, metavar="DIR",
                     help="snapshot state every --checkpoint-every iters "
                          "and auto-resume from DIR")
    run.add_argument("--checkpoint-every", type=int, default=100)

    ser = sub.add_parser("serial", help="serial baseline (golden reference)")
    _add_image_args(ser)
    ser.add_argument("-o", "--output", required=True)
    ser.add_argument("--filter", default="blur3", dest="filter_name")
    ser.add_argument("--impl", default="auto",
                     choices=["auto", "oracle", "native"],
                     help="auto = native C++ when built (bit-identical), "
                          "else the NumPy oracle")

    gen = sub.add_parser("generate", help="write a deterministic test image")
    gen.add_argument("output")
    gen.add_argument("rows", type=int)
    gen.add_argument("cols", type=int)
    gen.add_argument("mode", choices=["grey", "rgb"])
    gen.add_argument("--seed", type=int, default=0)

    cmp_ = sub.add_parser("compare", help="byte-compare two raw images")
    cmp_.add_argument("a")
    cmp_.add_argument("b")

    conv_ = sub.add_parser(
        "convert", help="raw -> PGM/PPM for visual inspection (no deps)"
    )
    conv_.add_argument("image")
    conv_.add_argument("rows", type=int)
    conv_.add_argument("cols", type=int)
    conv_.add_argument("mode", choices=["grey", "rgb"])
    conv_.add_argument("-o", "--output", required=True,
                       help=".pgm (grey) or .ppm (rgb) path")

    bench_p = sub.add_parser(
        "bench", help="time a synthetic workload; one JSON row to stdout"
    )
    bench_p.add_argument("rows", type=int)
    bench_p.add_argument("cols", type=int)
    bench_p.add_argument("loops", type=int)
    bench_p.add_argument("mode", choices=["grey", "rgb"])
    _add_perf_args(bench_p)
    bench_p.add_argument("--reps", type=int, default=3,
                         help="timing repetitions (min 1)")

    sub.add_parser("info", help="devices, default mesh, filters")

    args = ap.parse_args(argv)

    from parallel_convolution_tpu.utils import imageio

    if args.cmd == "generate":
        img = imageio.generate_test_image(args.rows, args.cols, args.mode,
                                          seed=args.seed)
        imageio.write_raw(args.output, img)
        print(f"wrote {args.output}: {args.rows}x{args.cols} {args.mode}")
        return 0

    if args.cmd == "compare":
        a = np.fromfile(args.a, dtype=np.uint8)
        b = np.fromfile(args.b, dtype=np.uint8)
        if a.shape == b.shape and np.array_equal(a, b):
            print("identical")
            return 0
        if a.shape != b.shape:
            print(f"size mismatch: {a.size} vs {b.size} bytes")
        else:
            n = int((a != b).sum())
            print(f"differ: {n} bytes ({100.0 * n / a.size:.4f}%), "
                  f"max delta {int(np.abs(a.astype(int) - b.astype(int)).max())}")
        return 1

    if args.cmd == "convert":
        img = imageio.read_raw(args.image, args.rows, args.cols, args.mode)
        magic = b"P5" if args.mode == "grey" else b"P6"
        with open(args.output, "wb") as f:
            f.write(magic + b"\n%d %d\n255\n" % (args.cols, args.rows))
            f.write(np.ascontiguousarray(img).tobytes())
        print(f"wrote {args.output} ({'PGM' if args.mode == 'grey' else 'PPM'})")
        return 0

    if args.cmd == "info":
        import jax
        from parallel_convolution_tpu.ops.filters import FILTERS
        from parallel_convolution_tpu.parallel.mesh import dims_create
        from parallel_convolution_tpu.utils.config import (
            BACKENDS, BOUNDARIES, STORAGES,
        )

        devs = jax.devices()
        print(f"backend: {jax.default_backend()}  devices: {len(devs)}")
        for d in devs[:8]:
            print(f"  {d}")
        print(f"default mesh: {dims_create(len(devs))}")
        print(f"filters: {', '.join(sorted(FILTERS))}")
        print(f"backends: {', '.join(BACKENDS)}")
        print(f"storages: {', '.join(STORAGES)}  "
              f"boundaries: {', '.join(BOUNDARIES)}")
        print("perf knobs: --fuse T, --tile TH,TW, "
              "--fast (measured flagship preset)")
        return 0

    if args.cmd == "serial":
        from parallel_convolution_tpu.ops import oracle
        from parallel_convolution_tpu.ops.filters import get_filter

        img = imageio.read_raw(args.image, args.rows, args.cols, args.mode)
        filt = get_filter(args.filter_name)
        impl = args.impl
        if impl in ("auto", "native"):
            try:
                from parallel_convolution_tpu.native import serial_native

                out = serial_native.run_serial_u8(img, filt, args.loops)
                impl = "native"
            except Exception:
                if impl == "native":
                    raise
                impl = "oracle"
        if impl == "oracle":
            out = oracle.run_serial_u8(img, filt, args.loops)
        imageio.write_raw(args.output, out)
        print(f"serial[{impl}]: {args.loops} x {args.filter_name} "
              f"-> {args.output}")
        return 0

    # run / bench share the tile flag and the compile cache; both need
    # the device from here on.
    from parallel_convolution_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    tile = None
    if getattr(args, "tile", None):
        try:
            tile = tuple(int(v) for v in args.tile.split(","))
            if len(tile) != 2 or min(tile) <= 0:
                raise ValueError
        except ValueError:
            ap.error(f"--tile must be TH,TW positive ints, got {args.tile!r}")

    if args.cmd == "bench":
        import json

        from parallel_convolution_tpu.ops.filters import get_filter
        from parallel_convolution_tpu.utils import bench as bench_lib

        mesh = _mesh_from_flag(args.mesh)
        _resolve_perf_knobs(args, mesh)
        row = bench_lib.bench_iterate(
            (args.rows, args.cols), get_filter(args.filter_name),
            args.loops, mesh=mesh,
            channels=3 if args.mode == "rgb" else 1,
            backend=args.backend, storage=args.storage, fuse=args.fuse,
            reps=args.reps, tile=tile, fallback=args.fallback,
            overlap=args.overlap, col_mode=args.col_mode,
        )
        print(json.dumps(row))
        return 0

    # run
    from parallel_convolution_tpu.models import ConvolutionModel, JacobiSolver

    mesh = _mesh_from_flag(args.mesh)
    _resolve_perf_knobs(args, mesh)
    if getattr(args, "rank", 2) == 3:
        return _run_volume(args, mesh)
    if args.solver != "jacobi" and args.converge is None:
        print(f"--solver {args.solver} requires --converge TOL: without "
              "it the run is a fixed-count iterate and the solver choice "
              "would be silently ignored", file=sys.stderr)
        return 2
    if args.solver == "multigrid" and args.storage != "f32":
        print(f"--solver multigrid requires --storage f32 (got "
              f"{args.storage}): residual/correction fields need full "
              "float carries", file=sys.stderr)
        return 2
    if args.converge is not None:
        mg = args.solver == "multigrid"
        solver = JacobiSolver(
            filt=args.filter_name, tol=args.converge, max_iters=args.loops,
            check_every=args.check_every, mesh=mesh, backend=args.backend,
            # Multigrid carries signed float residual/correction fields —
            # the u8 store-back would clamp the error equation (typed
            # ValueError in mg_converge); jacobi keeps the historical
            # quantized semantics.
            quantize=not mg, fuse=args.fuse, tile=tile,
            boundary=args.boundary, storage=args.storage, overlap=args.overlap,
            col_mode=args.col_mode,
            solver=args.solver, mg_levels=args.mg_levels,
        )
        img = imageio.read_raw(args.image, args.rows, args.cols, args.mode)
        x = imageio.interleaved_to_planar(img).astype(np.float32)
        out, iters = solver.solve(x)
        imageio.write_raw(
            args.output,
            imageio.planar_to_interleaved(
                np.clip(np.rint(out), 0, 255).astype(np.uint8)),
        )
        if mg and solver.last_mg is not None:
            res = solver.last_mg
            print(f"converged after {res.cycles} V-cycles "
                  f"({res.work_units} fine-grid work units, "
                  f"{res.levels} levels {res.level_shapes}, "
                  f"residual {res.residual:.3g}, tol {args.converge}) "
                  f"-> {args.output}")
        else:
            print(f"converged after {iters} iters (tol {args.converge}) "
                  f"-> {args.output}")
        return 0

    model = ConvolutionModel(filt=args.filter_name, mesh=mesh,
                             backend=args.backend, storage=args.storage,
                             fuse=args.fuse, boundary=args.boundary,
                             tile=tile,
                             overlap=args.overlap,
                             col_mode=args.col_mode,
                             fallback=args.fallback)
    if args.checkpoint:
        from parallel_convolution_tpu.parallel import step as step_lib
        from parallel_convolution_tpu.utils import checkpoint, sharded_io

        xs = sharded_io.load_sharded(args.image, args.rows, args.cols,
                                     args.mode, mesh)
        out = checkpoint.run_checkpointed(
            xs, model.filt, args.loops, mesh, (args.rows, args.cols),
            ckpt_dir=args.checkpoint, every=args.checkpoint_every,
            backend=args.backend, fuse=args.fuse, boundary=args.boundary,
            tile=tile,
            fallback=args.fallback, overlap=args.overlap,
            col_mode=args.col_mode,
        )
        sharded_io.save_sharded(args.output, out, args.rows, args.cols,
                                args.mode)
        if args.fallback:
            # run_checkpointed resolved per chunk inside iterate_prepared;
            # surface the process's last resolution so a degraded run is
            # labeled in the summary line, not only on stderr.
            from parallel_convolution_tpu.resilience import degrade

            req = args.backend
            if req == "auto":
                # The degrade walk saw the RESOLVED tier, never 'auto'.
                from parallel_convolution_tpu import tuning

                last = tuning.last_resolution()
                req = last.backend if last else req
            model.effective_backend = degrade.effective_for(req) or req
    elif args.sharded_io:
        model.run_raw_file_sharded(args.image, args.output, args.rows,
                                   args.cols, args.mode, args.loops)
    else:
        model.run_raw_file(args.image, args.output, args.rows, args.cols,
                           args.mode, args.loops)
    r, c = mesh.shape["x"], mesh.shape["y"]
    eff = getattr(model, "effective_backend", None) or args.backend
    if args.backend == "auto":
        # Auto-resolved, not degraded: label the tier AND where the plan
        # came from (measured|interpolated|predicted) so a mistune or a
        # missing plan file is visible in the summary line.  The
        # checkpoint branch resolves inside iterate_prepared (the model
        # object never runs), so fall back to the process's last
        # resolution for both pieces.
        from parallel_convolution_tpu import tuning

        last = tuning.last_resolution()
        src = getattr(model, "plan_source", "explicit")
        if src == "explicit" and last is not None:
            src = last.source
        if eff == "auto" and last is not None:
            eff = last.backend
        label = f"auto resolved to {eff} [{src}]"
    else:
        label = (args.backend if eff == args.backend
                 else f"{args.backend} degraded to {eff}")
    if getattr(model, "effective_overlap", None):
        label += ", overlapped halo pipeline"
    if getattr(model, "effective_col_mode", None) == "strided":
        label += ", strided column RDMA"
    print(f"ran {args.loops} x {args.filter_name} on {r}x{c} mesh "
          f"({label}) -> {args.output}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
