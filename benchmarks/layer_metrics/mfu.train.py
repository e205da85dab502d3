"""Model FLOPs per step (the family's count) / (step_device_ms.train x chips x peak bf16)."""
from benchmarks.harness.layer_lib import mfu as read  # noqa: F401
