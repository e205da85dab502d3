"""Share of device-busy time in tpu_custom_call events (the Pallas kernels), k_exaone.ragdocs_closed."""
from benchmarks.harness.layer_lib import mosaic_time_share as read  # noqa: F401
