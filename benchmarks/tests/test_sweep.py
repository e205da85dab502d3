"""``sweep.py``'s rule for a sustained rate, on the in-flight fifths that
PR 49's sweeps printed on the chip (``PERF.md`` section 2)."""
import pytest

from benchmarks import sweep


@pytest.mark.parametrize("rate,fifths,failed,ok", [
    (82, [26.0, 26.57, 28.5, 25.45, 25.6], 0, True),
    (94, [36.55, 36.1, 50.33, 41.1, 30.65], 0, True),
    # a hot middle is the schedule's, not a backlog
    (96, [32.42, 36.25, 55.67, 45.77, 34.35], 0, True),
    # the end stands 14 over the start: the backlog grew through the window
    (98, [42.5, 40.33, 57.3, 82.42, 56.55], 0, False),
    # a full queue sheds, and the backlog stops growing for that reason
    (120, [60.0, 200.0, 288.0, 288.0, 60.0], 31, False),
])
def test_a_rate_is_sustained_where_the_backlog_does_not_grow(rate, fifths,
                                                             failed, ok):
    assert sweep.sustained(fifths, failed) is ok
