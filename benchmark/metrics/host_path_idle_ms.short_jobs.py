"""``host_path_idle_ms`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.host_path_idle_ms import read  # noqa: F401
