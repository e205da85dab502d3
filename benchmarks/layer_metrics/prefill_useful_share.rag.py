"""Prompt tokens admitted / token slots the admission calls ran over, R(bucket) rows x the chunk's bucket a call (counters admit_tokens / admit_token_slots): one row a call since PR 32, so what is lost is the row's padding to its bucket, joyai_flash.ragdocs_closed."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
