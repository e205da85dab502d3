"""window_decode (paged_decode over the per-slot rings), the six window layers: the live slots' 128 ring rows of K and V of 8 x 128 lanes (the step-local queries and contexts not counted) over its mean traced time, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import window_decode_roofline_share as read  # noqa: F401
