"""``kernel_io_ms`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.kernel_io_ms import read  # noqa: F401
