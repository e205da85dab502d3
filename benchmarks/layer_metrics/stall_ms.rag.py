"""How far the longest single interval of any loop phase inside the window outlasted every interval of that phase before it (largest delta of loop_max_us_<phase>), joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import stall_ms as read  # noqa: F401
