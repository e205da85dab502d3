"""Share of device-busy time in ops under the linear-attention layers' named scope 'gdn' or in the gated_delta kernels (union of their intervals over the traced window)."""
from benchmarks.harness.hybrid_lib import gdn_time_share as read  # noqa: F401
