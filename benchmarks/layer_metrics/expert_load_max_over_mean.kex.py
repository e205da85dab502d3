"""The busiest held expert's routed tokens over the mean held expert's, decode steps of the window, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import expert_load_max_over_mean as read  # noqa: F401
