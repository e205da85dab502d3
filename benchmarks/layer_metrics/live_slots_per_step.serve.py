"""Tokens of requests completed in the window / decode steps in the window (engine counters tokens, decode_steps)."""
from benchmarks.harness.layer_lib import live_slots_per_step as read  # noqa: F401
