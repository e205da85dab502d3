"""The busiest held expert's routed tokens over the mean held expert's, decode steps of the window, summed over the expert layers, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import expert_load_max_over_mean as read  # noqa: F401
