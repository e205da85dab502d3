"""Mean time from submit to a decode slot over requests admitted in the window (counters queue_wait_us / admit_rows), open-loop chat cells."""
from benchmarks.harness.engine_lib import queue_wait_mean_ms as read  # noqa: F401
