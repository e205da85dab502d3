"""Window seconds / decode steps taken in the window (engine counter decode_steps), open-loop chat cells."""
from benchmarks.harness.layer_lib import step_wall_ms as read  # noqa: F401
