"""Mean time from submit to the first token on the host over requests admitted in the window (counters ttft_us / admit_rows), open-loop chat cells."""
from benchmarks.harness.engine_lib import ttft_mean_ms as read  # noqa: F401
