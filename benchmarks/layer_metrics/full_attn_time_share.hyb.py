"""Share of device-busy time in ops under the full-attention layers' named scope 'attn' or in the paged_decode kernel (union of their intervals over the traced window)."""
from benchmarks.harness.hybrid_lib import full_attn_time_share as read  # noqa: F401
