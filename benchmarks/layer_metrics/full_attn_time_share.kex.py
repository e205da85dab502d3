"""Share of device-busy time in ops under the global layers' named scope 'attn' or in the paged_decode / flash_fwd_grouped kernels, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import full_attn_time_share as read  # noqa: F401
