"""How far the longest single interval of any loop phase inside the window outlasted every earlier one, qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import stall_ms as read  # noqa: F401
