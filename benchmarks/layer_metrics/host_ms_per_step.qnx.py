"""Host phases of the loop (sched, admit.host, decode.pack, harvest, publish) per decode step, qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import host_ms_per_step as read  # noqa: F401
