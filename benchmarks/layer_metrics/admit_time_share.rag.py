"""Share of the loop's non-waiting time in admission (host and device phases), joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_time_share as read  # noqa: F401
