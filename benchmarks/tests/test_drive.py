"""``runners/serve_engine.py:drive`` at the engine's door: an open loop's
caller that is refused (a full queue sheds) comes again after a backoff and
its wait is in its latency; a closed loop's caller fails at once."""
import contextlib
from concurrent.futures import Future

import numpy as np

from benchmarks.harness import loader

runner = loader.load_module("runners", "serve_engine")


class Door:
    """An engine that answers at once, after refusing each of the first
    ``refuse`` offers of the request whose prompt starts with 7."""

    class metrics:
        snapshot = staticmethod(dict)

    def __init__(self, refuse):
        self.refuse, self.offers = refuse, 0

    def submit(self, prompt, n):
        if prompt[0] == 7:
            self.offers += 1
            if self.offers <= self.refuse:
                raise RuntimeError("queue depth 256 at limit 256 - load shed")
        f = Future()
        f.set_result(np.zeros(n, np.int32))
        return f


def _span(name):
    return contextlib.nullcontext()


def test_an_open_loop_s_caller_comes_again_and_its_wait_is_counted():
    reqs = [{"due_s": 0.01 * k, "prompt": [7 if k == 1 else 1],
             "max_new_tokens": 3} for k in range(4)]
    recs, window, _ = runner.drive(Door(2), {"loop": "open"}, reqs, 0.5,
                                   _span)
    assert len(window) == 4 and all(r.error is None for r in recs)
    assert [r.refused for r in recs] == [0, 2, 0, 0]
    assert all(len(r.tokens) == 3 for r in recs)
    # 0.1 s, then 0.2 s of backoff, on top of the moment it was due
    assert recs[1].done - recs[1].due >= 0.3 > recs[0].done - recs[0].due


def test_a_caller_refused_for_good_has_failed_and_a_closed_loop_s_at_once():
    reqs = [{"due_s": 0.0, "prompt": [7], "max_new_tokens": 3}]
    import time
    real, t0 = time.perf_counter, time.perf_counter()
    # the minute past the close, in a twentieth of the time
    time.perf_counter = lambda: t0 + 20 * (real() - t0)
    try:
        recs, window, _ = runner.drive(Door(10 ** 9), {"loop": "open"},
                                       reqs, 0.5, _span)
    finally:
        time.perf_counter = real
    assert window[0].error and window[0].done is not None
    assert window[0].tokens is None and window[0].refused > 10
    recs, window, _ = runner.drive(
        Door(1), {"loop": "closed", "clients": 1},
        [{"due_s": None, "prompt": [7], "max_new_tokens": 3}], 0.2, _span)
    assert recs[0].error and recs[0].refused == 1 and recs[0].done is not None
    assert all(r.error is None for r in recs[1:]) and len(recs) > 1
