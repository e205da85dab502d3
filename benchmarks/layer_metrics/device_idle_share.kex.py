"""1 - union of the device's op intervals over the traced window, k_exaone.ragdocs_closed."""
from benchmarks.harness.layer_lib import device_idle_share as read  # noqa: F401
