"""Share of the loop's time outside waits spent admitting (host + device phases), qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import admit_time_share as read  # noqa: F401
