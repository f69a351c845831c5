"""The plain reference: iterated 2D cross-correlation with u8 store-back.

Independent of the program under test: it imports nothing of it and takes
its filter from the configuration file.  Semantics (the reference's serial
``convolute`` loop): every iteration zero-pads the image by the filter
radius, accumulates ``tap * pixel`` over the taps in row-major order in the
stated precision, rounds half to even, clips to [0, 255] and stores u8.

On the device it runs where its input lies: a (C, H, W) image sharded
over a mesh is recomputed one channel at a time in that same sharding,
and XLA partitions the zero pad and the tap slices by itself (its own
ghost exchange, not the program's), so no chip ever holds the whole
image and a fault in the program's exchange cannot hide in the reference.

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


def _plane_sharding(x):
    """The sharding of one (H, W) channel of ``x``: its mesh and the
    spec of its last two axes; None for an unsharded input."""
    from jax.sharding import NamedSharding, PartitionSpec

    sh = getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec) + (None,) * (3 - len(sh.spec))
    return NamedSharding(sh.mesh, PartitionSpec(*spec[1:]))


@functools.lru_cache(maxsize=None)
def _plane_runner(taps, iters: int, precision: str, sharding):
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

    def keep(v):  # a sharded plane stays in its sharding, step by step
        if sharding is None:
            return v
        return lax.with_sharding_constraint(v, sharding)

    def one(v):
        H, W = v.shape
        p = jnp.pad(v, r)  # u8: a sharded plane's ghost bands move as bytes
        acc = None
        for dy in range(k):
            for dx in range(k):
                px = p[dy:dy + H, dx:dx + W].astype(jnp.float32)
                term = rnd(jnp.float32(taps[dy][dx]) * px)
                acc = term if acc is None else rnd(acc + term)
        return keep(jnp.clip(jnp.round(acc), 0, 255).astype(jnp.uint8))

    def run(x, c):
        v = keep(x[c].astype(jnp.uint8))
        return lax.fori_loop(0, iters, lambda _, u: one(u), v)

    return jax.jit(run, static_argnums=1, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _stacker(sharding):
    import jax
    import jax.numpy as jnp

    return jax.jit(jnp.stack, out_shardings=sharding)


def device_run(x, taps, iters: int, precision: str = "float32"):
    """(C, H, W) array of u8 values -> u8, one channel at a time so it
    fits; a sharded ``x`` gives an output in its own sharding."""
    plane = _plane_sharding(x)
    run = _plane_runner(tuple(map(tuple, taps)), iters, precision, plane)
    planes = [run(x, c) for c in range(x.shape[0])]
    return _stacker(None if plane is None else x.sharding)(planes)


@functools.lru_cache(maxsize=None)
def _differ():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        d = jnp.abs(a.astype(jnp.int32) - b.astype(jnp.int32))
        return jnp.count_nonzero(d), jnp.max(d)

    return jax.jit(f)


def compare(got, want) -> dict:
    """Mismatched bytes and the widest byte gap, computed on the device
    where the two arrays lie: sharded arrays are reduced across their
    chips, and neither is gathered onto one."""
    if tuple(got.shape) != tuple(want.shape):
        return {"mismatched_bytes": int(np.prod(want.shape)),
                "max_abs_diff": 255}
    n, m = _differ()(got, want)
    return {"mismatched_bytes": int(n), "max_abs_diff": int(m)}
