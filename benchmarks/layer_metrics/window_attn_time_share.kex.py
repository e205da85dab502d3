"""Share of device-busy time in ops under the window layers' named scope 'win' or in the flash_fwd_window / window_decode kernels, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import window_attn_time_share as read  # noqa: F401
