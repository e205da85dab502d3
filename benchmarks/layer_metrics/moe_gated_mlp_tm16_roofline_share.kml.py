"""Decode's expert kernel: the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s (the matrices of the experts HELD AND TOUCHED once, the local pairs' rows in and out) over its mean traced time, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import decode_expert_kernel_roofline_share as read  # noqa: F401
