"""``device_idle_share`` in the cells whose rate is ``gpx_per_s_per_chip.short_jobs``."""

from benchmark.metrics.device_idle_share import read  # noqa: F401
