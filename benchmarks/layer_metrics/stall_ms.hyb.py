"""How far the longest interval of any loop phase inside the window outlasted every one before it, olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import stall_ms as read  # noqa: F401
