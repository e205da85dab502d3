"""Share of device-busy time in ops under the expert layers' named scope 'moe' or in the moe_gated_mlp kernels, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import moe_time_share as read  # noqa: F401
