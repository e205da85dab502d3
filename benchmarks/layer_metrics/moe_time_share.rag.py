"""Share of device-busy time in ops under the expert layers' named scope 'moe' or in the grouped gated-MLP kernel (union of their intervals over the traced window)."""
from benchmarks.harness.latent_moe_lib import moe_time_share as read  # noqa: F401
