"""paged_decode at the decode width: the swept pages' K and V rows of 2 x 256 lanes (kv_pages_swept_steps) over its mean traced time, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import paged_decode_roofline_share as read  # noqa: F401
