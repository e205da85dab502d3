"""Share of device-busy time in ops under the expert layers' named scope 'moe' or in the moe_gated_mlp kernels, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import moe_time_share as read  # noqa: F401
