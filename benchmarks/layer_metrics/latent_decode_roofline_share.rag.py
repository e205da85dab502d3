"""The decode step's latent page walk (latent_decode, PR 45): the larger of FLOPs / 197 TFLOP/s and bytes / 819 GB/s (the swept pages' 640-lane rows once, from kv_pages_swept_steps; queries in, contexts out) over its mean traced time, one event a latent layer, joyai_flash.ragdocs_closed."""
from benchmarks.harness.latent_moe_lib import latent_decode_roofline_share as read  # noqa: F401
