"""1 - union of op intervals on device 0's XLA Ops line over the traced span, olmo_hybrid.ragdocs_closed."""
from benchmarks.harness.layer_lib import device_idle_share as read  # noqa: F401
