"""What one job asks of the chip, counted from its shapes alone.

The count reads the same work whatever implements it, so no kernel change
can make it stale: the direct stencil's multiply-adds (2 operations per tap
per pixel-iteration, even where a separable or fused kernel does fewer) and
one u8 read and one u8 write of the image per job.  Peaks come from
``peaks.json``, keyed by ``device_kind``; an unknown kind is an error.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak_for(kind: str, path: str = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peak for device kind {kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[kind]


def stencil_ops(n_taps: int, pixels: int, iters: int) -> int:
    return 2 * n_taps * pixels * iters


def image_bytes(pixels: int, bytes_per_pixel: int = 1) -> int:
    return 2 * pixels * bytes_per_pixel


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which of the two bounds sets it."""
    compute = ops / peak["flops_per_s"]
    memory = nbytes / peak["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
