"""Admission's prompt-attention kernel: the larger of useful causal-pair FLOPs / 197 TFLOP/s and bytes / 819 GB/s (queries, keys, values, context once) over its mean traced time, one event a layer."""
from benchmarks.harness.latent_moe_lib import prefill_attention_roofline_share as read  # noqa: F401
