"""Decode steps dispatched while the step before them was still unread / decode steps, x 100 (counters decode_steps_ahead, decode_steps), k_exaone.ragdocs_closed."""
from benchmarks.harness.ahead_lib import decode_ahead_share as read  # noqa: F401
