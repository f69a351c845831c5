"""``gpx_per_s_per_chip`` in the one-chip cells of short jobs, where the
host call per job sets a wider spread, and so a bound of its own."""

from benchmark.metrics.gpx_per_s_per_chip import read  # noqa: F401
