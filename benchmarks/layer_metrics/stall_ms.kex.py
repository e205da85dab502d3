"""How far the longest single interval of a loop phase inside the window outlasted every earlier one (counters loop_max_us_*), k_exaone.ragdocs_closed."""
from benchmarks.harness.engine_lib import stall_ms as read  # noqa: F401
