"""Host phases of the loop (sched, admit_host, decode_pack, harvest, publish) / decode steps, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.engine_lib import host_ms_per_step as read  # noqa: F401
