"""Share of device-busy time in ops under the expert layers' named scope 'moe' or in the moe_gated_mlp kernels, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import moe_time_share as read  # noqa: F401
