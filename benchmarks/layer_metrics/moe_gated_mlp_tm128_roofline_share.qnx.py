"""Admission's expert kernel: useful prompt tokens x top-k x the local pair share as rows, every held expert's matrices once, over its mean traced time, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import admit_expert_kernel_roofline_share as read  # noqa: F401
