"""Mean over decode steps and expert layers of the HELD experts that got at least one token (counters moe_experts_touched / moe_layer_steps), k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import experts_touched_per_step as read  # noqa: F401
