"""The general generator for closed-loop traffic, driven by a traffic file.

One client keeps one job in flight: it calls the job, fences its output
with ``block_until_ready`` and only then issues the next, back to back
until the window's seconds have passed.  The window runs from its start to
the last completed fence.

Input: one seeded noise image, made on the device during set-up, stays
resident, and every job takes it; each output is dropped after its fence.
So nothing but the jobs runs in the window, no chain settles on a fixed
point of the stencil, and a job that returns its input unchanged fails the
check.  The image is float32 with u8 values, the type ``run_planar``
takes: the entry converts (and may donate) its own copy, so the resident
image survives every job.
"""

from __future__ import annotations

import dataclasses
import time

CHECK_BEFORE = 0.5  # the checked job starts in the window's first half


def device_image(shape, seed: int, sharding=None):
    """A (C, H, W) float32 image of u8 values, made on the device from
    ``seed`` (any size) in one jitted call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    word = int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])
    make = jax.jit(
        lambda k: jax.random.bits(k, shape, jnp.uint8).astype(jnp.float32),
        out_shardings=sharding)
    return make(jax.random.key(word))


def check_fraction(seed: int) -> float:
    """Where in the window the checked job starts, drawn from the seed."""
    import numpy as np

    return float(np.random.default_rng(seed % 2**64).random()) * CHECK_BEFORE


@dataclasses.dataclass
class Window:
    jobs: int
    seconds: float
    kept: object            # the checked job's output
    kept_index: int | None  # its index among the window's jobs
    job_s: list[float]      # each job's seconds, issue to fence


def run(job, image, seconds: float, keep_at: float) -> Window:
    """Drive ``job(image)`` back to back for ``seconds``; keep one answer.

    ``job(x)`` issues one job and returns its (not yet fenced) output.
    """
    import jax

    annotate = jax.profiler.TraceAnnotation
    kept, kept_index, n, job_s = None, None, 0, []
    t0 = t = time.perf_counter()
    keep_t = t0 + keep_at * seconds
    with annotate("bench.window"):
        while True:
            keep = kept is None and time.perf_counter() >= keep_t
            with annotate("bench.job"):
                y = job(image)
                with annotate("bench.fence"):
                    y.block_until_ready()
            if keep:
                kept, kept_index = y, n
            del y
            n += 1
            job_s.append(time.perf_counter() - t)
            t += job_s[-1]
            if t - t0 >= seconds:
                break
    return Window(n, t - t0, kept, kept_index, job_s)


def warm_up(job, image) -> None:
    """Compile and run everything the window runs, once."""
    job(image).block_until_ready()
    if image.is_deleted():  # a float32 storage plan donates its input
        raise ValueError("the entry consumed the resident image: the "
                         "closed loop has no input for the next job")
