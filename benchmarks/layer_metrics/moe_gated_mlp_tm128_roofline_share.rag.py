"""Admission's grouped gated-MLP kernel: the larger of useful-row FLOPs / 197 TFLOP/s and bytes / 819 GB/s (all experts' matrices once, rows in and out) over its mean traced time."""
from benchmarks.harness.latent_moe_lib import prefill_kernel_roofline_share as read  # noqa: F401
