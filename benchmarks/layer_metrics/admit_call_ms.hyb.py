"""Engine-loop time inside the admission device calls / admission calls (counters loop_us_admit_device, admit_steps), olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
