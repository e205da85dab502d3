"""Engine-loop time inside the admission's device calls / admission calls (counters loop_us_admit_device, admit_steps), k_exaone.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
