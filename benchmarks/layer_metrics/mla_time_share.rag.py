"""Share of device-busy time in ops under the latent attention's named scope 'mla' (union of their intervals over the traced window)."""
from benchmarks.harness.latent_moe_lib import mla_time_share as read  # noqa: F401
