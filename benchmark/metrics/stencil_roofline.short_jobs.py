"""``stencil_roofline`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.stencil_roofline import read  # noqa: F401
