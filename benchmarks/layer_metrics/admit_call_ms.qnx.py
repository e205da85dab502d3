"""Engine-loop time inside the admission calls / calls (counters loop_us_admit_device, admit_steps; a call is R(bucket) = 2 rows x the bucket), qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
