"""Share of device-busy time in ops under the KDA layers' named scope 'kda' (projections, conv and WY operands included) or in the kda_chunk / kda_step kernels, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import kda_time_share as read  # noqa: F401
