"""The one-token state kernel: every slot's float32 state in and out (32 value heads of 128 x 128; q, k of the 16 key heads) over its mean traced time, qwen3_next.longgen_closed."""
from benchmarks.harness.qwen3_next_lib import step_kernel_roofline_share as read  # noqa: F401
