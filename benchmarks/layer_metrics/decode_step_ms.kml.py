"""Engine-loop time inside the decode step's device call / decode steps (counters loop_us_decode_device, decode_steps), kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.engine_lib import decode_step_ms as read  # noqa: F401
