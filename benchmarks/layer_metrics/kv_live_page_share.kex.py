"""Live pages over page-table entries a step, the two global layers' pages (counters kv_pages_live_steps / kv_page_slots_steps), k_exaone.ragdocs_closed."""
from benchmarks.harness.engine_lib import kv_live_page_share as read  # noqa: F401
