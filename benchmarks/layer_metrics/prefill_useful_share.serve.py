"""Prompt tokens prefilled / token slots of the [B x bucket] admission programs that ran (counters admit_tokens / admit_token_slots), closed-loop cells."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
