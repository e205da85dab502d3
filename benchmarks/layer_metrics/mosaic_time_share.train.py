"""Share of device-busy time in tpu_custom_call events (the repo's Pallas kernels), training cells."""
from benchmarks.harness.layer_lib import mosaic_time_share as read  # noqa: F401
