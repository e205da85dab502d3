"""Share of the engine loop's non-waiting time spent admitting (host and device phases), olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import admit_time_share as read  # noqa: F401
