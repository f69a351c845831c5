"""``step_host_ms`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.step_host_ms import read  # noqa: F401
