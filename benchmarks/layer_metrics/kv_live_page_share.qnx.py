"""Mapped page-table entries over B x G per decode step (rows of 2 x 256 lanes), qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import kv_live_page_share as read  # noqa: F401
