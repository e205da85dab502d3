"""Share of device-busy time in ops under the latent layers' named scope 'mla' or in the latent_prefill_attention kernel, kimi_linear.longdoc_gen_closed."""
from benchmarks.harness.kimi_linear_lib import mla_time_share as read  # noqa: F401
