"""Tokens of requests completed in the window / decode steps in the window, open-loop chat cells."""
from benchmarks.harness.layer_lib import live_slots_per_step as read  # noqa: F401
