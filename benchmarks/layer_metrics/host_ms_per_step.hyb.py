"""Host phases of the engine loop (sched, admit.host, decode.pack, harvest, publish) per decode step, olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import host_ms_per_step as read  # noqa: F401
