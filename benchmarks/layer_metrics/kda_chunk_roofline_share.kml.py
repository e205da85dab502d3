"""kda_chunk (the walk over a prompt's chunks, one decay a key channel): the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s of what the kernel reads and writes for the call's REAL prompt tokens, over its mean traced time, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import chunk_kernel_roofline_share as read  # noqa: F401
