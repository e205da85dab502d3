"""Page-table entries that hold a live page / entries the decode kernel's grid covers, B x G a step (counters kv_pages_live_steps / kv_page_slots_steps), closed-loop cells."""
from benchmarks.harness.engine_lib import kv_live_page_share as read  # noqa: F401
