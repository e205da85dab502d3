"""Share of the loop's non-waiting time in admission, host and device (counters loop_us_admit_host + loop_us_admit_device over loop_us_total - loop_us_wait), kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.engine_lib import admit_time_share as read  # noqa: F401
