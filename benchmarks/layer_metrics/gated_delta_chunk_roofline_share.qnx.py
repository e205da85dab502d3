"""The walk over a prompt's chunks: its useful tokens' five float32 operands in, dv out, per value head, over its mean traced time, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import chunk_kernel_roofline_share as read  # noqa: F401
