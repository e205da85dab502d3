"""How far the longest single interval of any loop phase inside the window outlasted every interval of that phase before it (the largest delta of the loop_max_us_<phase> counters): milliseconds when steady, seconds when the process stood still; closed-loop cells."""
from benchmarks.harness.engine_lib import stall_ms as read  # noqa: F401
