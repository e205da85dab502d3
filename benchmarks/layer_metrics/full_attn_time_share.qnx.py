"""Share of device-busy time in ops under the full-attention layers' named scope 'attn' or in the paged_decode kernel, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import full_attn_time_share as read  # noqa: F401
