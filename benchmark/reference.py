"""The plain reference: iterated 2D cross-correlation with u8 store-back.

Independent of the program under test: it imports nothing of it and takes
its filter from the configuration file.  Semantics (the reference's serial
``convolute`` loop): every iteration zero-pads the image by the filter
radius, accumulates ``tap * pixel`` over the taps in row-major order in the
stated precision, rounds half to even, clips to [0, 255] and stores u8.

``precision`` is the precision of every multiply and add.  ``float32`` is
the configuration's; ``bfloat16`` is the control, the nearest precision
below it: each product and partial sum is rounded to bfloat16
(``lax.reduce_precision``, so no compiler may keep excess precision).
"""

from __future__ import annotations

import functools

import numpy as np

BF16_BITS = (8, 7)  # exponent, mantissa


def taps_of(config: dict) -> tuple[tuple[float, ...], ...]:
    """The filter taps as float32 values, from the configuration file."""
    w = np.asarray(config["weights"], dtype=np.float64) / config["divisor"]
    return tuple(tuple(float(np.float32(v)) for v in row) for row in w)


def numpy_run(img: np.ndarray, taps, iters: int) -> np.ndarray:
    """(C, H, W) u8 -> (C, H, W) u8 in float32, on the host (small sizes)."""
    t = np.asarray(taps, dtype=np.float32)
    k = t.shape[0]
    r = k // 2
    out = np.asarray(img, dtype=np.uint8)
    C, H, W = out.shape
    for _ in range(iters):
        p = np.pad(out.astype(np.float32), ((0, 0), (r, r), (r, r)))
        acc = np.zeros((C, H, W), np.float32)
        for dy in range(k):
            for dx in range(k):
                acc = acc + t[dy, dx] * p[:, dy:dy + H, dx:dx + W]
        out = np.clip(np.rint(acc), 0, 255).astype(np.uint8)
    return out


@functools.lru_cache(maxsize=None)
def _plane_runner(taps, iters: int, precision: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    k = len(taps)
    r = k // 2
    if precision == "float32":
        rnd = lambda v: v  # noqa: E731 — f32 ops round to f32 already
    elif precision == "bfloat16":
        rnd = lambda v: lax.reduce_precision(v, *BF16_BITS)  # noqa: E731
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def one(v):
        H, W = v.shape
        p = jnp.pad(v.astype(jnp.float32), r)
        acc = None
        for dy in range(k):
            for dx in range(k):
                term = rnd(jnp.float32(taps[dy][dx]) * p[dy:dy + H, dx:dx + W])
                acc = term if acc is None else rnd(acc + term)
        return jnp.clip(jnp.round(acc), 0, 255).astype(jnp.uint8)

    return jax.jit(lambda v: lax.fori_loop(0, iters, lambda _, u: one(u), v))


def device_run(x, taps, iters: int, precision: str = "float32"):
    """(C, H, W) device array of u8 values -> u8, one channel at a time so
    it fits."""
    import jax.numpy as jnp

    run = _plane_runner(tuple(map(tuple, taps)), iters, precision)
    return jnp.stack([run(x[c].astype(jnp.uint8)) for c in range(x.shape[0])])


@functools.lru_cache(maxsize=None)
def _differ():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        d = jnp.abs(a.astype(jnp.int32) - b.astype(jnp.int32))
        return jnp.count_nonzero(d), jnp.max(d)

    return jax.jit(f)


def compare(got, want) -> dict:
    """Mismatched bytes and the widest byte gap, computed on the device."""
    if tuple(got.shape) != tuple(want.shape):
        return {"mismatched_bytes": int(np.prod(want.shape)),
                "max_abs_diff": 255}
    n, m = _differ()(got, want)
    return {"mismatched_bytes": int(n), "max_abs_diff": int(m)}
