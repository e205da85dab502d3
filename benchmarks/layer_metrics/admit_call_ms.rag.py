"""Engine-loop time inside the admission's device calls / calls (counters loop_us_admit_device, admit_steps): a call is one row x the chunk's bucket (every ragdocs_closed bucket is past 1024: R(bucket) = 1 since PR 32), joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
