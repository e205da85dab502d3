"""Mapped K/V pages / page-table entries of all slots, per decode step (the four full-attention layers' pools), olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import kv_live_page_share as read  # noqa: F401
