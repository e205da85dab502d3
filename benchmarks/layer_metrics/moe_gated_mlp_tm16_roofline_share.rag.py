"""Decode's grouped gated-MLP kernel: the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s (touched experts' matrices once, rows in and out) over its mean traced time."""
from benchmarks.harness.latent_moe_lib import decode_kernel_roofline_share as read  # noqa: F401
