"""Live slots per decode step, counted at dispatch (counters live_slot_steps / decode_steps), closed-loop cells."""
from benchmarks.harness.engine_lib import decode_live_slots as read  # noqa: F401
