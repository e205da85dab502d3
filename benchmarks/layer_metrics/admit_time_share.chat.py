"""Share of the engine loop's non-waiting time spent admitting: (loop_us_admit_host + loop_us_admit_device) / (loop_us_total - loop_us_wait), open-loop chat cells."""
from benchmarks.harness.engine_lib import admit_time_share as read  # noqa: F401
