"""Real prompt tokens over the token slots the admission calls computed (counters admit_tokens / admit_token_slots), k_exaone.ragdocs_closed."""
from benchmarks.harness.engine_lib import prefill_useful_share as read  # noqa: F401
