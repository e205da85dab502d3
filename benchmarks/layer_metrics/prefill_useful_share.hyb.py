"""Prompt tokens admitted / token slots of the admission calls (R x bucket a call), olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
