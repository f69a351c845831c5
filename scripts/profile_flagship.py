#!/usr/bin/env python
"""Profiler-trace check of the DESIGN.md VPU-ceiling claim (run on TPU).

DESIGN.md's roofline asserts the fused separable kernel is compute-bound
at ~1.47 TF/s f32 VPU throughput.  That figure was *derived* (op ledger ×
fenced wall), never confirmed by a device trace.  This script:

1. times the flagship workload (blur3, pallas_sep, bf16, fuse=T),
2. captures ONE execution of the compiled runner under
   ``jax.profiler.trace`` into ``evidence/traces/`` (xplane protobuf,
   parsed offline — tracing a full bench_iterate would record every
   repetition and inflate the capture),
3. prints a JSON row holding the wall plus both DESIGN.md ledger
   conventions side by side, so the chip leg confirms or corrects the
   claim under the SAME accounting DESIGN.md uses:
     - flops/px = 2·2k = 12 for blur3 separable (FMA = 2 flops, MACs
       only) → ``implied_vpu_gflops`` compares against 1 469.8,
     - ops/px/level = 2k FMA + 1 rint + 2 masks = 9 post-elision
       (FMA = 1 op) → ``implied_vpu_gops`` compares against ~1 350.

Usage (chip session):
  python scripts/profile_flagship.py --size 8192 --fuse 32 --reps 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import _path  # noqa: F401  (repo root onto sys.path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--fuse", type=int, default=32)
    ap.add_argument("--backend", default="pallas_sep")
    ap.add_argument("--storage", default="bf16")
    ap.add_argument("--tile", default="1024x512")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--trace-dir", default="evidence/traces")
    args = ap.parse_args()

    import jax
    import numpy as np

    from parallel_convolution_tpu.ops.filters import get_filter
    from parallel_convolution_tpu.parallel import step as step_lib
    from parallel_convolution_tpu.parallel.mesh import make_grid_mesh
    from parallel_convolution_tpu.utils import bench
    from parallel_convolution_tpu.utils.platform import on_tpu
    from parallel_convolution_tpu.utils.tracing import device_trace

    mesh = make_grid_mesh(jax.devices()[:1], (1, 1))
    filt = get_filter("blur3")
    tile = tuple(int(v) for v in args.tile.split("x"))
    kw = dict(mesh=mesh, backend=args.backend, storage=args.storage,
              fuse=args.fuse, tile=tile, reps=args.reps)

    # 1. Fenced wall (the number the roofline divides by).
    row = bench.bench_iterate((args.size, args.size), filt, args.iters, **kw)

    # 2. Trace exactly ONE execution of the compiled runner (compile +
    #    warmup happen before the trace starts).
    trace_dir = os.path.join(args.trace_dir,
                             f"flagship_{args.size}_fuse{args.fuse}")
    os.makedirs(trace_dir, exist_ok=True)
    # The same launch bench_iterate resolved, so the build cache hits
    # and the already-compiled runner is reused (a second 8192^2 Mosaic
    # compile would add minutes to the run).
    launch = step_lib.resolve_launch(
        mesh, filt, (1, args.size, args.size), backend=args.backend,
        storage=args.storage, fuse=args.fuse, tile=tile)
    xs, valid_hw, _ = step_lib._prepare(
        np.random.default_rng(0)
        .integers(0, 256, size=(1, args.size, args.size))
        .astype(np.float32), mesh, launch)
    fn = step_lib._iterate_runner(mesh, filt, args.iters, True, valid_hw,
                                  launch)
    out = bench.fence(fn(xs))  # compile + warm, outside the trace
    with device_trace(trace_dir):
        out = bench.fence(fn(out))

    # 3. Both DESIGN.md ledger conventions (see module docstring).
    k = filt.size
    flops_px = 2 * 2 * k            # 12 for blur3: MACs only, FMA = 2
    ops_px = 2 * k + 1 + 2          # 9 post-elision: + rint + 2 masks
    gpx = row["gpixels_per_s_per_chip"]
    row.update(
        trace_dir=trace_dir,
        flops_per_px=flops_px,
        implied_vpu_gflops=round(gpx * flops_px, 1),   # vs 1469.8 claimed
        ops_per_px_level=ops_px,
        implied_vpu_gops=round(gpx * ops_px, 1),       # vs ~1350 derived
        on_tpu=on_tpu(),
    )
    print(json.dumps(row), flush=True)

    return 0


if __name__ == "__main__":
    sys.exit(main())
