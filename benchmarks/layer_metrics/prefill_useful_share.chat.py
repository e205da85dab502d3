"""Prompt tokens admitted / token slots the admission calls ran over, R(bucket) rows x the chunk's bucket a call (counters admit_tokens / admit_token_slots): what is lost is a call's second row left empty and each row's padding to its bucket, gpt2_small.chat_open."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
