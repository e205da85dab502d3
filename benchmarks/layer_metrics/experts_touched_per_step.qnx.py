"""Mean over decode steps and expert layers of the HELD experts that got at least one token (counters moe_experts_touched / moe_layer_steps), qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import experts_touched_per_step as read  # noqa: F401
