"""Mean over decode steps and expert layers of the HELD experts that got at least one token (counters moe_experts_touched / moe_layer_steps), kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import experts_touched_per_step as read  # noqa: F401
