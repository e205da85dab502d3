"""Device time of one optimizer step: median duration of the step program on the XLA Modules line / steps chained."""
from benchmarks.harness.layer_lib import step_device_ms as read  # noqa: F401
