"""flash_fwd_window: 4 x H x hd x sum_p min(p + 1, 128) FLOPs over the call's REAL prompt tokens (and q, k, v in, contexts out) over its mean traced time; blocks swept and masked earn nothing, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import window_prefill_roofline_share as read  # noqa: F401
