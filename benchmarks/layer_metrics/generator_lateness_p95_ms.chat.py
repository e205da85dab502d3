"""95th percentile of actual send time minus due time, the benchmark's own load generator."""
from benchmarks.harness.layer_lib import generator_lateness_p95_ms as read  # noqa: F401
