"""The loop's host phases (sched, admit.host, decode.pack, harvest, publish) per decode step, joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import host_ms_per_step as read  # noqa: F401
