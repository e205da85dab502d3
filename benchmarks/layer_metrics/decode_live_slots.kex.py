"""Live slots a decode step (counters live_slot_steps / decode_steps), k_exaone.ragdocs_closed."""
from benchmarks.harness.engine_lib import decode_live_slots as read  # noqa: F401
