"""The two NoPE latent layers' decode-step page walk (latent_decode, PR 45): latent_moe_lib's count (the swept pages' 640-lane rows once, from kv_pages_swept_steps; queries in, contexts out) over its mean traced time, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import latent_decode_roofline_share as read  # noqa: F401
