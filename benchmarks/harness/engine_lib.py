"""Arithmetic over the serving engine's own counters, as the runner
differences them over the window (``ev["facts"]["counters"]``; the program
keeps them in ``paddle_tpu/serving/metrics.py:LOOP_COUNTERS``).  Every
number is a ratio of two counter deltas, and none uses ``ev["seconds"]``: the two snapshots fall at arbitrary phases of a step, and
the loop adds all of an iteration's counters under one lock, so numerator
and divisor always cover the same iterations.  A reader returns None, and
its metric is left out, where a counter is missing (a program from before
they existed) or the divisor is 0."""

HOST_PHASES = ("loop_us_sched", "loop_us_admit_host", "loop_us_decode_pack",
               "loop_us_harvest", "loop_us_publish")


def _ratio(ev, num, den, scale):
    """``scale x sum(num) / sum(den)`` over counter names."""
    c = ev["facts"].get("counters") or {}
    if any(k not in c for k in (*num, *den)):
        return None
    d = sum(c[k] for k in den)
    return scale * sum(c[k] for k in num) / d if d > 0 else None


def decode_step_ms(ev):
    return _ratio(ev, ("loop_us_decode_device",), ("decode_steps",), 1e-3)


def host_ms_per_step(ev):
    return _ratio(ev, HOST_PHASES, ("decode_steps",), 1e-3)


def decode_live_slots(ev):
    return _ratio(ev, ("live_slot_steps",), ("decode_steps",), 1.0)


def prefill_useful_share(ev):
    return _ratio(ev, ("admit_tokens",), ("admit_token_slots",), 100.0)


def kv_live_page_share(ev):
    return _ratio(ev, ("kv_pages_live_steps",), ("kv_page_slots_steps",),
                  100.0)


def queue_wait_mean_ms(ev):
    return _ratio(ev, ("queue_wait_us",), ("admit_rows",), 1e-3)


def ttft_mean_ms(ev):
    return _ratio(ev, ("ttft_us",), ("admit_rows",), 1e-3)


def admit_call_ms(ev):
    return _ratio(ev, ("loop_us_admit_device",), ("admit_steps",), 1e-3)

