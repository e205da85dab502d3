"""paged_decode at the decode width, the two global layers: the swept pages' K and V rows of 8 x 128 lanes (kv_pages_swept_steps) over its mean traced time, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import paged_decode_roofline_share as read  # noqa: F401
