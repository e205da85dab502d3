"""Prompt tokens over the admission calls' token slots (admit_tokens / admit_token_slots), qwen3_next.longgen_closed."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
