"""Prompt tokens admitted / token slots the admission calls ran over, 2 rows x the chunk's bucket a call (counters admit_tokens / admit_token_slots), joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
