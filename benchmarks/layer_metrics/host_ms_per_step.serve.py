"""Engine-loop host time per decode step: (sched + admit_host + decode_pack + harvest + publish) / decode_steps, closed-loop cells."""
from benchmarks.harness.engine_lib import host_ms_per_step as read  # noqa: F401
