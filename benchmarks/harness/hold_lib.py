"""How many of the admitted requests waited for a partner, from the engine's
own counters over the window (``harness/engine_lib.py`` says how they are
differenced): ``admit_rows_held`` counts the rows of a short last admission
chunk that the serving loop put back to wait for the slot that fills the
call, once a row however many iterations it waited
(``paddle_tpu/serving/metrics.py:LOOP_COUNTERS``, PR 38;
``admit_hold_slot_steps`` beside it, in ``counter_deltas``, is what the
waiting cost: decode steps dispatched with a free slot so held).  None, and
the metric is left out, for a program from before the counter."""
from benchmarks.harness.engine_lib import _ratio


def admit_held_row_share(ev):
    return _ratio(ev, ("admit_rows_held",), ("admit_rows",), 100.0)
