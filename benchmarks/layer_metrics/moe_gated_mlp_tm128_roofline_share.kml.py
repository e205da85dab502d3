"""Admission's expert kernel: useful prompt tokens x top-k x the local pair share as rows, every held expert's matrices once, over its mean traced time, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import admit_expert_kernel_roofline_share as read  # noqa: F401
