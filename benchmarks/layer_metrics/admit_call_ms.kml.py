"""Engine-loop time inside the admission's device calls / admission calls (counters loop_us_admit_device, admit_steps), kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
