"""1 - union of the device's op intervals over the traced window, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.layer_lib import device_idle_share as read  # noqa: F401
