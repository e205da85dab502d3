"""(Query tile, key block) pairs the paged_decode kernel walks in the admission calls, each tile to its own sweep bound / the tiles of the bucket x the slot's bound, x 100 (counters admit_attn_blocks_walked, admit_attn_blocks_square), gpt2_small.docs_closed."""
from benchmarks.harness.walk_lib import admit_attn_walked_share as read  # noqa: F401
