"""Share of device-busy time in ops under the linear-attention layers' named scope 'gdn' or in the gated_delta kernels, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import gdn_time_share as read  # noqa: F401
