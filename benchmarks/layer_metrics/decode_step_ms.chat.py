"""Engine-loop time inside the decode step's device call (the _step call until its tokens are on the host) / decode steps (counters loop_us_decode_device, decode_steps), open-loop chat cells."""
from benchmarks.harness.engine_lib import decode_step_ms as read  # noqa: F401
