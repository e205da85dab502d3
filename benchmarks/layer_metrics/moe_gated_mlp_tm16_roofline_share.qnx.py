"""Decode's expert kernel: the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s (the matrices of the experts HELD AND TOUCHED once, the local pairs' rows in and out) over its mean traced time, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import decode_expert_kernel_roofline_share as read  # noqa: F401
