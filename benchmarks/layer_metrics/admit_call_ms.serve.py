"""Engine-loop time inside one admission's device call (the _padmit call, a prefill over all rows x the bucket, until its first tokens are on the host) / admission calls (counters loop_us_admit_device, admit_steps), closed-loop cells."""
from benchmarks.harness.engine_lib import admit_call_ms as read  # noqa: F401
