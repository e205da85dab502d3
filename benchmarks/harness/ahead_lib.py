"""How often the serving loop ran one decode step ahead, from the engine's
own counters over the window (``harness/engine_lib.py`` says how they are
differenced): ``decode_steps_ahead`` counts the decode steps dispatched
while the step before them was still unread on the device
(``paddle_tpu/serving/metrics.py:LOOP_COUNTERS``, PR 34).  None, and the
metric is left out, for a program from before the counter."""
from benchmarks.harness.engine_lib import _ratio


def decode_ahead_share(ev):
    return _ratio(ev, ("decode_steps_ahead",), ("decode_steps",), 100.0)
