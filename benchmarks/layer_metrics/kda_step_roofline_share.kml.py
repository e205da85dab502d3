"""kda_step: every slot's float32 state in and out (2 x 32 x 128 x 128 x 4 B a slot and layer) and its rows, over its mean traced time, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import step_kernel_roofline_share as read  # noqa: F401
