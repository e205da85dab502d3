"""Busiest expert's routed tokens over the mean expert's, decode steps of the window, summed over the expert layers (the engine's per-expert counts)."""
from benchmarks.harness.latent_moe_lib import expert_load_max_over_mean as read  # noqa: F401
