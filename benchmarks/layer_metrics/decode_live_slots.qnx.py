"""Live slots per decode step (counters live_slot_steps / decode_steps), qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import decode_live_slots as read  # noqa: F401
