"""``launches_per_job`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.launches_per_job import read  # noqa: F401
