"""Engine-loop time inside admission device calls / calls of [2 x bucket] rows (counters loop_us_admit_device, admit_steps), joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
