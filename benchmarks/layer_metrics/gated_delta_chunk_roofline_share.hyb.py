"""Admission's walk over a prompt's chunks: the larger of useful-token FLOPs / 197 TFLOP/s and float32 operand bytes / 819 GB/s over the kernel's mean traced time, one event a linear layer."""
from benchmarks.harness.hybrid_lib import chunk_kernel_roofline_share as read  # noqa: F401
