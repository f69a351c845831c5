#!/usr/bin/env python
"""Price VPU ops with an in-VMEM Pallas chain, to steer kernel-ledger work.

Context: two silicon A/Bs (an unmasked-interior launch split
1.004x, fused-path clamp elision ~0-3%) falsified the uniform-op-cost ledger — removing
"ops" only pays when the removed op sits on the issue-critical path.
DESIGN.md names the credible next levers as cutting *FMA or rint* work,
e.g. integer accumulation folding rint into the u8 store, or the
magic-number rint replacement.  Whether those levers can pay depends on
hardware op prices this probe measures directly:

  - f32 FMA chain         — the kernel's dominant op (baseline price)
  - bf16 FMA chain        — packed-2x issue?
  - int32 / int16 mul-add — the integer-accumulate alternative's price
  - int32 / int16 add     — the blur numerator's actual op mix
  - f32 rint (+add)       — the per-level quantize cost being folded
  - f32 magic-round (+add)— (x + 1.5*2^23) - 1.5*2^23, the candidate
                            2-add replacement for rint (exact
                            half-to-even for |x| < 2^22)
  - f32 clamp (min+max+add) — the already-elided op, for scale
  - f32 add               — chain control (subtract from rint rows)

METHOD.  Each candidate is a Pallas kernel whose grid streams
(1024, 512) blocks through VMEM and runs K dependent elementwise steps
per block via an in-kernel fori_loop — so HBM traffic is one read +
one write per block while compute is K ops/element (~32 f32 ops/byte
at K=128): issue-bound by two orders of magnitude.  This exists
because two cheaper attempts measured something else (artifacts kept
alongside, 2026-07-31):

  - vpu_op_probe_r5_stream.jsonl: jitted fori_loop(unroll=8) chain —
    every dtype landed at ~700 GB/s regardless of op: HBM-bound.
  - vpu_op_probe_r5_xla_chain.jsonl: Python-unrolled 128-op jit chain —
    internally inconsistent (pure f32 add "7x slower" than f32 FMA;
    the slow rows' walls exactly match 128 unfused round trips): it
    measures XLA's fusion grouping, not the VPU.

One JSON row per candidate: {op, dtype, ops_per_step, elems, k,
wall_s, gops_per_s, per_step_vs_f32_fma}.  ``per_step_vs_f32_fma`` is
the price of one step of this op chain in units of one f32-FMA step.
"""

from __future__ import annotations

import json
import sys
from functools import partial

import _path  # noqa: F401

MAGIC = 12582912.0  # 1.5 * 2**23: f32 add forces round-half-even at ulp=1


def main() -> int:
    from parallel_convolution_tpu.utils.platform import (
        enable_compile_cache,
    )

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from parallel_convolution_tpu.utils import bench

    H, W = 8192, 512   # 4M elements; streamed as 16 VMEM blocks
    BH = 512           # block rows: 4 refs x 1 MB f32 x 2 slots = 8 MB,
    #                    inside the 16 MB scoped-VMEM budget (1024 rows
    #                    OOM'd at ~22 MB)
    K = 128            # dependent steps per element

    rng = np.random.default_rng(0)
    xf = rng.uniform(10.0, 200.0, (H, W)).astype(np.float32)
    # Multiplier near 1 and a sign-alternating addend keep K chained
    # steps inside float range (no inf/NaN slow paths).
    af = rng.uniform(0.99, 1.01, (H, W)).astype(np.float32)
    bf = rng.uniform(-0.5, 0.5, (H, W)).astype(np.float32)
    xi = rng.integers(0, 255, (H, W)).astype(np.int32)
    ai = rng.integers(1, 4, (H, W)).astype(np.int32)
    bi = rng.integers(-8, 8, (H, W)).astype(np.int32)

    interpret = jax.default_backend() == "cpu"

    def runner(step, a, b, dtype):
        """Chainable x -> x: grid-streamed blocks, K in-VMEM steps each."""
        def kern(x_ref, a_ref, b_ref, o_ref):
            av, bv = a_ref[...], b_ref[...]

            def body(_, y):
                return step(y, av, bv)

            # Full unroll (Mosaic supports only unroll=1 or =num_steps):
            # amortizes per-iteration loop overhead so the wall prices
            # the op, not the loop.
            o_ref[...] = jax.lax.fori_loop(0, K, body, x_ref[...],
                                           unroll=K)

        spec = pl.BlockSpec((BH, W), lambda i: (i, 0))
        call = pl.pallas_call(
            kern,
            grid=(H // BH,),
            in_specs=[spec, spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((H, W), dtype),
            interpret=interpret,
        )
        aj = jnp.asarray(a, dtype=dtype)
        bj = jnp.asarray(b, dtype=dtype)
        return jax.jit(lambda x: call(x, aj, bj))

    platform = jax.default_backend()
    candidates = [
        # (op, dtype_name, dtype, ops/step, step(y, a, b), x0)
        ("fma", "f32", jnp.float32, 1, lambda y, a, b: y * a + b, xf),
        ("fma", "bf16", jnp.bfloat16, 1, lambda y, a, b: y * a + b, xf),
        ("muladd", "i32", jnp.int32, 1, lambda y, a, b: y * a + b, xi),
        ("muladd", "i16", jnp.int16, 1, lambda y, a, b: y * a + b, xi),
        ("add", "i32", jnp.int32, 1, lambda y, a, b: y + b, xi),
        ("add", "i16", jnp.int16, 1, lambda y, a, b: y + b, xi),
        ("add", "f32", jnp.float32, 1, lambda y, a, b: y + b, xf),
        # rint/magic rows keep values moving with +b so the chain cannot
        # collapse; subtract the add-f32 row to price the round alone.
        ("rint+add", "f32", jnp.float32, 2,
         lambda y, a, b: jnp.rint(y) + b, xf),
        ("magicround+add", "f32", jnp.float32, 3,
         lambda y, a, b: ((y + MAGIC) - MAGIC) + b, xf),
        ("clamp+add", "f32", jnp.float32, 3,
         lambda y, a, b: jnp.minimum(jnp.maximum(y, 0.0), 255.0) + b, xf),
    ]

    rows = []
    f32_fma_step = None
    for name, dtype_name, dtype, ops, step, x0 in candidates:
        try:
            if dtype_name.startswith("i"):
                a_src, b_src = ai, bi
            else:
                a_src, b_src = af, bf
            run = runner(step, a_src, b_src, dtype)
            x = jnp.asarray(x0, dtype=dtype)
            wall_s = bench.wall(run, x, reps=5)
        except Exception as e:
            msg = repr(e)
            if len(msg) > 600:
                msg = msg[:300] + " ... " + msg[-300:]
            print(json.dumps({"op": name, "dtype": dtype_name,
                              "error": msg}), flush=True)
            continue
        total_ops = H * W * K * ops
        row = {
            "op": name, "dtype": dtype_name, "ops_per_step": ops,
            "elems": H * W, "k": K, "wall_s": round(wall_s, 6),
            "gops_per_s": round(total_ops / wall_s / 1e9, 1),
            "platform": platform,
        }
        per_step = wall_s / K
        if name == "fma" and dtype_name == "f32":
            f32_fma_step = per_step
        if f32_fma_step:
            row["per_step_vs_f32_fma"] = round(per_step / f32_fma_step, 3)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
