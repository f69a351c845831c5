#!/usr/bin/env python
"""Tile/fuse sweep for the Pallas stencil kernels (run on a real TPU).

Since round 9 this is a thin CLI over ``tuning.search`` — the sweep
loop, candidate legality, and the winner pick live there (shared with
``backend="auto"`` and ``scripts/tune.py``), not here.  Flags are
unchanged from the round-1 tool.  Prints a JSON row per measured point
(resolved tile/fuse stamped by ``utils.bench``) and the winner; to
persist the winner as a plan file use ``scripts/tune.py --emit-plans``.

  python scripts/tune_pallas.py --size 8192 --iters 20
"""

from __future__ import annotations

import argparse
import json
import sys

import _path  # noqa: F401  (repo root onto sys.path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--storage", default="bf16")
    ap.add_argument("--backend", default="pallas",
                    choices=["pallas", "pallas_sep"])
    ap.add_argument("--tiles", default=None,
                    help="comma list of HxW tiles, e.g. 1024x512,128x512 "
                         "(default: the tuning.search menu, legality-"
                         "filtered)")
    ap.add_argument("--fuses", default=None,
                    help="comma list of fusion depths, e.g. 16,32,64")
    args = ap.parse_args()

    import jax

    from parallel_convolution_tpu.ops.filters import get_filter
    from parallel_convolution_tpu.parallel.mesh import make_grid_mesh
    from parallel_convolution_tpu.tuning import Workload, search

    mesh = make_grid_mesh(jax.devices()[:1], (1, 1))
    filt = get_filter("blur3")
    w = Workload.from_mesh(mesh, filt, (1, args.size, args.size),
                           storage=args.storage)

    tiles = None
    if args.tiles:
        tiles = [tuple(int(v) for v in t.split("x"))
                 for t in args.tiles.split(",")]
    fuses = None
    if args.fuses:
        fuses = tuple(int(v) for v in args.fuses.split(","))
    candidates = search.enumerate_candidates(
        w, backends=[args.backend], fuses=fuses, tiles=tiles)
    # A requested point the legality filter dropped must leave a row —
    # the pre-round-9 tool benched it and recorded the compile error;
    # silently incomplete evidence is worse than either.
    legal_tiles = {c.tile for c in candidates}
    legal_fuses = {c.fuse for c in candidates}
    for t in (tiles or []):
        if tuple(t) not in legal_tiles:
            print(json.dumps({"tile": f"{t[0]}x{t[1]}", "error":
                              "dropped: fails (sublane,128) alignment, "
                              "scoped-VMEM budget, or block-size legality"}),
                  flush=True)
    for f in (fuses or []):
        if f not in legal_fuses:
            print(json.dumps({"fuse": f, "error":
                              "dropped: fails block>=r*T (or the tiled-"
                              "RDMA r*T<=sublane bound)"}), flush=True)
    results = []
    # tile/fuse thread through as explicit static jit arguments inside
    # search.measure -> bench_iterate — monkeypatching module defaults
    # does NOT reach already-traced kernels.
    for _, c in search.rank(w, candidates):
        try:
            row = search.measure(w, c, mesh, iters=args.iters, reps=2)
            results.append(row)
            print(json.dumps(row), flush=True)
        except Exception as e:  # noqa: BLE001 — an illegal point is data
            print(json.dumps({
                "tile": f"{c.tile[0]}x{c.tile[1]}" if c.tile else None,
                "fuse": c.fuse, "error": repr(e)[:150]}), flush=True)

    if results:
        best = max(results, key=lambda r: r["gpixels_per_s_per_chip"])
        print(f"# BEST: {json.dumps(best)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
