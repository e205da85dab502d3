"""Admission's expert kernel (width-tiled): useful prompt tokens x top-k x the local pair share as rows, every held expert's matrices ONCE (a second row tile's re-read is time, not bytes), over its mean traced time, k_exaone.ragdocs_closed."""
from benchmarks.harness.k_exaone_lib import admit_expert_kernel_roofline_share as read  # noqa: F401
