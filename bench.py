#!/usr/bin/env python
"""Driver benchmark: prints ONE JSON line with the headline metric.

Headline (BASELINE.json): Gpixels/sec/chip, 3×3 blur (the reference's own
kernel), 100 iterations, uint8 store-back semantics — measured on whatever
accelerator is attached (the driver runs this on the real TPU chip).

``vs_baseline``: the reference's published MPI numbers were unreadable
(empty mount), so the ratio is against the
honestly-measured single-process CPU serial baseline (C++ serial binary if
built, else the NumPy oracle) on the reference's canonical 1920×2520 image —
i.e. "TPU chips vs the serial C-class baseline", the same speedup the
reference's README tables report for MPI ranks vs serial.
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    import jax

    from parallel_convolution_tpu.utils.platform import (
        enable_compile_cache, on_tpu,
    )

    enable_compile_cache()
    # A measurement path that finds no chip fails; it never times the CPU
    # under a device metric's name.
    if not on_tpu():
        print(f"bench.py: no TPU attached (default backend "
              f"{jax.default_backend()!r}); refusing to publish a row",
              file=sys.stderr)
        return 1

    from parallel_convolution_tpu.ops.filters import get_filter
    from parallel_convolution_tpu.parallel.mesh import make_grid_mesh
    from parallel_convolution_tpu.utils import bench

    platform = jax.default_backend()
    n_dev = len(jax.devices())
    mesh = make_grid_mesh()
    filt = get_filter("blur3")

    # Silicon guard for the magic-number round default: the compiled
    # Mosaic kernels rely on Mosaic NOT algebraically folding
    # (acc + 1.5*2^23) - 1.5*2^23 (XLA:CPU folds it; the interpret-mode
    # tests therefore exercise the barrier form, never the bare form
    # silicon runs).  One tiny quantized kernel vs the NumPy oracle per
    # driver round: if a Mosaic/jax upgrade ever starts folding, the
    # rounding vanishes and this byte-compare catches it loudly before
    # a throughput row is published.
    import jax.numpy as jnp
    import numpy as np

    from parallel_convolution_tpu.ops import oracle, pallas_stencil
    from parallel_convolution_tpu.utils import imageio

    gimg = imageio.generate_test_image(128, 256, "grey", seed=5)
    gwant = oracle.run_serial_u8(gimg, filt, 2)
    gx = imageio.interleaved_to_planar(gimg).astype(np.float32)
    gout = gx
    for _ in range(2):
        gout = pallas_stencil.correlate_shifted_pallas(
            jnp.asarray(gout), filt, quantize=True)
    ggot = imageio.planar_to_interleaved(np.asarray(gout).astype(np.uint8))
    magic_guard = "ok" if np.array_equal(ggot, gwant) else "MISMATCH"
    if magic_guard != "ok":
        print("# MAGIC-ROUND GUARD FAILED: compiled kernel bytes "
              "diverge from the oracle — Mosaic may have started "
              "folding the two-add round; see _round_mode_for",
              file=sys.stderr)
    elif pallas_stencil._MAGIC_GUARD.get("ok") is False:
        # The library-level probe caught the fold FIRST and already
        # flipped every compiled kernel to rint — so the bytes above
        # compare clean.  The fold event itself is still the terminal
        # condition this guard exists to surface (a silent ~14% perf
        # regression plus an unverified-compiler state), so report it
        # as MISMATCH rather than letting the self-heal hide it.
        magic_guard = "MISMATCH"
        print("# MAGIC-ROUND GUARD: library probe detected the fold and "
              "fell back to rint — published bytes are correct, but the "
              "magic-round assumption is broken on this jax/Mosaic; see "
              "_compiled_magic_ok", file=sys.stderr)

    shape, iters, reps = (8192, 8192), 100, 3

    # xla_conv at 8192² OOMs on v5e (XLA's conv lowering materializes a
    # ~34 GB intermediate for 1-channel NCHW); bench it at 4096² — still
    # saturating — so the comparison row exists.
    configs = [
        ("shifted", "f32", 1, shape),
        ("xla_conv", "f32", 1, (min(shape[0], 4096), min(shape[1], 4096))),
        ("pallas", "f32", 1, shape),
        ("shifted", "bf16", 4, shape),
        ("pallas", "bf16", 8, shape),
        ("pallas_sep", "bf16", 16, shape),
        ("pallas_sep", "bf16", 32, shape),
        # u8 carries: the reference's own buffer dtype — quarter the HBM
        # traffic of f32; exact by construction in quantize mode.
        ("pallas_sep", "u8", 16, shape),
        ("pallas_sep", "u8", 32, shape),
        # RDMA tier at a tiled-kernel-sized block: degenerate (no remote
        # partner) on a 1x1 mesh, but every driver round re-proves the
        # kernel + barrier compile and run on real silicon.  fuse=4 adds
        # the in-kernel temporal fusion row (T*r-deep exchange + T levels
        # per launch) — the tier's reason-to-exist lever; the RDMA-vs-
        # ppermute small-block A/B lives in scripts/rdma_fuse_ab.py.
        ("pallas_rdma", "f32", 1,
         (min(shape[0], 2048), min(shape[1], 2048))),
        ("pallas_rdma", "f32", 4,
         (min(shape[0], 2048), min(shape[1], 2048))),
    ]
    candidates = {}
    for backend, storage, fuse, cshape in configs:
        name = f"{backend}/{storage}/fuse{fuse}"
        if cshape != shape:
            # Off-default shape must be visible in the candidate name so
            # wall_s values across rows can't be misread as comparable.
            name += f"@{cshape[0]}"
        try:
            row = bench.bench_iterate(
                cshape, filt, iters, mesh=mesh, backend=backend,
                storage=storage, fuse=fuse, reps=reps,
            )
            candidates[name] = row
            print(f"# {name}: {row}", file=sys.stderr)
        except Exception as e:  # keep the bench robust: one line, always
            print(f"# {name} failed: {e!r}", file=sys.stderr)
    if not candidates:
        print(json.dumps({"metric": "Gpixels/sec/chip (3x3 conv, 100 iters)",
                          "value": 0.0, "unit": "Gpixels/s/chip",
                          "vs_baseline": 0.0, "error": "all backends failed"}))
        return 1

    best_name, best = max(
        candidates.items(), key=lambda kv: kv[1]["gpixels_per_s_per_chip"]
    )

    proxy = bench.bench_oracle_proxy(iters=2)
    print(f"# serial proxy: {proxy}", file=sys.stderr)

    # Halo p50: on a multi-device mesh this is the real number; on the
    # 1×1 single-chip mesh bench_halo_p50 refuses (there is no collective
    # to time) and the honest record is null + a labeled CPU-mesh
    # functional proxy from a clean subprocess.
    halo_row = {}
    try:
        halo_row = bench.bench_halo_p50((512, 512), r=1, mesh=mesh)
        print(f"# halo: {halo_row}", file=sys.stderr)
    except Exception as e:
        print(f"# halo bench failed: {e!r}", file=sys.stderr)
    halo_proxy = {}
    if halo_row.get("mesh") == "1x1":
        # Only the single-chip case earns the proxy; a null from a REAL
        # multi-device mesh (noise floor, error) must stay an explained
        # null, not be papered over with a CPU number.
        from parallel_convolution_tpu.utils import halo_proxy as hp

        halo_proxy = hp.run_in_subprocess()
        print(f"# halo cpu-mesh proxy: {halo_proxy}", file=sys.stderr)

    value = best["gpixels_per_s_per_chip"]
    result = {
        "metric": "Gpixels/sec/chip (3x3 conv, 100 iters)",
        "value": value,
        "unit": "Gpixels/s/chip",
        "vs_baseline": round(value / proxy["gpixels_per_s"], 2),
        "platform": platform,
        # What the winning row ACTUALLY ran on (bench_iterate stamps
        # every row).
        "effective_backend": best.get("effective_backend"),
        "row_platform": best.get("platform"),
        "devices": n_dev,
        "best_backend": best_name,
        "workload": best["workload"],
        "wall_s": best["wall_s"],
        "halo_p50_us": halo_row.get("p50_us"),
        "serial_proxy_gpixels_per_s": proxy["gpixels_per_s"],
        "serial_proxy_impl": proxy["impl"],
        # Denominator provenance: median-of-N with spread, so vs_baseline
        # swings can be attributed (the single-trial proxy moved ±20%
        # between identical-code rounds r01-r03).
        "serial_proxy_reps": proxy.get("reps"),
        "serial_proxy_spread_pct": proxy.get("spread_pct"),
        "magic_round_guard": magic_guard,
    }
    if halo_row.get("unmeasurable"):
        result["halo_p50_note"] = halo_row["unmeasurable"]
    for key in ("noise_floor", "clamped_trials"):
        if halo_row.get(key):
            result[f"halo_{key}"] = halo_row[key]
    if halo_proxy.get("p50_us") is not None:
        # Labeled functional proxy: same compiled ppermute exchange, 8
        # virtual CPU devices — mechanism + magnitude, not ICI latency.
        result["halo_p50_cpu_mesh_proxy_us"] = halo_proxy["p50_us"]
        result["halo_p50_proxy_mesh"] = halo_proxy.get("mesh")
    print(json.dumps(result))
    # A failed magic-round guard means the compiled kernels' bytes are
    # wrong — publish the labeled row (the guard field names the cause)
    # but exit nonzero so automation cannot treat the run as healthy.
    return 1 if magic_guard == "MISMATCH" else 0


if __name__ == "__main__":
    sys.exit(main())
