"""Mean over decode steps and expert layers of the experts that got at least one token (counters moe_experts_touched / moe_layer_steps), of 256."""
from benchmarks.harness.latent_moe_lib import experts_touched_per_step as read  # noqa: F401
