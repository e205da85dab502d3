"""Of the (token, choice) pairs the decode steps' routers made, the share whose expert this chip holds (counters moe_pairs_local / moe_pairs_routed), k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import moe_local_pair_share as read  # noqa: F401
