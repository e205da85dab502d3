"""Decode's in-place state update: the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s (every slot's float32 state in and out) over the kernel's mean traced time, one event a linear layer."""
from benchmarks.harness.hybrid_lib import step_kernel_roofline_share as read  # noqa: F401
