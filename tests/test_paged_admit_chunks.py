"""The paged admission prefill runs over a few rows, a bucket's own count
(serving/generation.py ``admit_rows``: ``_ADMIT_ROWS`` where they fit
``_ADMIT_TOKEN_SLOTS`` token slots, fewer past that): an iteration that
admits n requests packs them in admission order into chunks
(``admit_chunks``) and dispatches one program of ``[R(bucket), bucket]`` a
chunk, each over its own rows' page-table rows.  Served tokens stay the
uncached greedy reference's whatever n is and wherever the buckets fall
about the cap, siblings of one prefix may fall in different chunks, a
quantized pool and per-row adapter ids follow their rows, and the compile
set stays ``len(prompt_buckets) + 2``.

A short LAST chunk waits (``hold_pays``): where it is short for want of a
slot, with a request still queued behind full slots, and the next live slot
ends by count inside the break-even of the loop's own mean step and call,
its rows go back to the head of the queue and the freed slot's row fills the
call.  The tests make the two means definite by pacing the engine's own
device calls, never by setting anything of the rule's.
"""
import contextlib
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving import generation

R = generation._ADMIT_ROWS
B = 2 * R + 1  # a full burst is two whole chunks and a partial one
BUCKETS, PAGE, CACHE = [8, 16], 8, 64
#: the compile set: one admission program a bucket, the step, the page copy,
#: and wherever several devices make up the global mesh (the suite's eight)
#: the step's fresh-pool trace (GenerationEngine.warmup's docstring)
COMPILE_SET = len(BUCKETS) + 2 + int(len(jax.devices()) > 1)
BURSTS = {"one": 1, "a_chunk": R, "a_chunk_and_one": R + 1, "every_slot": B}


def _prompt(k):
    # lengths 3..14 over both buckets, so a chunk mixes buckets
    return (np.arange(3 + (k * 5) % 12) * (k + 3) + k) % 97


def _gpt(**cfg):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    pt.seed(4321)
    m = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                                 num_heads=4, max_position=CACHE,
                                 dropout=0.0, **cfg))
    m.eval()
    return m


def _ref_greedy(model, prompt, n):
    import jax.numpy as jnp

    ids, outs = list(map(int, prompt)), []
    for _ in range(n):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(ids)] = ids
        logits = np.asarray(model(jnp.asarray(padded)))[0]
        outs.append(int(np.argmax(logits[len(ids) - 1])))
        ids.append(outs[-1])
    return outs


def _engine(model, name, **kw):
    return GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=B,
                            cache_len=CACHE, kv_page_size=PAGE,
                            speculative_k=0, name=name, **kw)


def _burst(eng, requests):
    """Submit ``requests`` (prompt, budget, submit keywords) so that ONE
    iteration of the loop admits them all: the queue's lock is held while
    they are enqueued, so the loop's poll sees none of them or all.
    Returns the served tokens and the counters the burst added."""
    for _ in range(500):  # the loop is idle: every earlier iteration flushed
        before = eng.metrics.snapshot()
        if before["evicted"] == before["admitted"]:
            break
        time.sleep(0.01)
    with eng._batcher._cv:
        futs = [eng.submit(p, n, **kw) for p, n, kw in requests]
    outs = [f.result(120).tolist() for f in futs]
    for _ in range(500):
        snap = eng.metrics.snapshot()
        if snap["evicted"] - before["evicted"] >= len(requests):
            return outs, {k: snap[k] - before[k] for k in (
                "batches", "admit_steps", "admit_rows", "admit_row_slots",
                "admit_tokens", "admit_token_slots", "admit_rows_held",
                "admit_hold_slot_steps", "handoffs_in")}
        time.sleep(0.01)
    raise AssertionError("the loop never flushed its last iteration")


def _one_admitting_iteration(delta, requests):
    n = len(requests)
    assert (delta["batches"], delta["admit_rows"]) == (1, n)
    assert delta["admit_steps"] == -(-n // R)
    assert delta["admit_tokens"] == sum(len(p) for p, _, _ in requests)
    # every chunk is R rows of a bucket
    assert delta["admit_row_slots"] == R * delta["admit_steps"]
    assert delta["admit_token_slots"] % R == 0
    assert R * BUCKETS[0] * delta["admit_steps"] \
        <= delta["admit_token_slots"] <= R * BUCKETS[-1] * delta["admit_steps"]


@pytest.fixture(scope="module")
def model():
    return _gpt()


@pytest.fixture(scope="module")
def engine(model):
    with _engine(model, "chunks") as eng:
        assert eng.warmup() == COMPILE_SET
        yield eng


def test_the_row_chunk_is_derived_from_the_batch(model, engine):
    assert engine._admit_rows == {b: R for b in BUCKETS} and R < B
    with GenerationEngine(model, prompt_buckets=[8], batch_size=1,
                          cache_len=CACHE, kv_page_size=PAGE,
                          speculative_k=0, name="chunks-b1") as one:
        assert one._admit_rows == {8: 1}


# -- rows follow the bucket ---------------------------------------------------
CAP = generation._ADMIT_TOKEN_SLOTS


@pytest.mark.parametrize("buckets,batch,rows", [
    ([64, 128, 256, 512], 32, [R] * 4),                  # chat_open
    ([512, 640, 768], 32, [R] * 3),                      # docs_closed
    ([CAP // R], 4, [R]),                                # at the cap
    ([CAP // R + 1], 4, [R - 1]),                        # the first past it
    ([1536, 2048, 3072, 4096], 32, [1] * 4),             # ragdocs_closed
    ([CAP // R, CAP, 3 * CAP], 16, [R, 1, 1]),           # never under one
    ([64, CAP // R, 4096], 1, [1, 1, 1]),                # one slot
], ids=["chat", "docs", "at", "past", "ragdocs", "straddle", "one_slot"])
def test_rows_of_a_bucket(buckets, batch, rows):
    assert [generation.admit_rows(b, batch) for b in buckets] == rows


@pytest.mark.parametrize("buckets,chunks", [
    ([], []),
    ([8], [[0]]),
    ([8, 8, 8], [[0, 1], [2]]),
    ([16, 16], [[0], [1]]),
    ([8, 16], [[0], [1]]),           # the wide row does not fit beside it
    ([16, 8, 8], [[0], [1, 2]]),
    ([8, 16, 8], [[0], [1], [2]]),   # a short chunk in the middle
    ([8, 16, 8, 8, 16], [[0], [1], [2, 3], [4]]),
], ids=lambda v: "-".join(map(str, v)) if v and isinstance(v[0], int)
    else None)
def test_chunks_keep_admission_order_and_hold_rows_of_their_bucket(
        buckets, chunks):
    assert generation.admit_chunks(buckets, {8: 2, 16: 1}.get) == chunks


def _prompt_in(k, bucket):
    # a prompt that routes to `bucket` of BUCKETS: 3..8 or 9..14 tokens
    n = 3 + (k * 5) % 6 + (6 if bucket == BUCKETS[-1] else 0)
    return (np.arange(n) * (k + 3) + k) % 97


@pytest.fixture(scope="module")
def straddling(model):
    """An engine whose buckets lie on both sides of the cap: two rows of 8,
    one row of 16."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generation, "_ADMIT_TOKEN_SLOTS", BUCKETS[-1])
        eng = _engine(model, "straddle")
    with eng:
        assert eng._admit_rows == {8: 2, 16: 1}
        assert eng.warmup() == COMPILE_SET
        yield eng


#: bursts one iteration admits, by their rows' buckets, and the calls they
#: make: (bucket, rows filled) a call
MIXED = {
    "one": ([16], [(16, 1)]),
    "two": ([8, 16], [(8, 1), (16, 1)]),
    "three": ([8, 16, 8], [(8, 1), (16, 1), (8, 1)]),
    "every_slot": ([8, 8, 16, 16, 8], [(8, 2), (16, 1), (16, 1), (8, 1)]),
}


@pytest.mark.parametrize("burst", MIXED.values(), ids=MIXED.keys())
def test_a_burst_of_mixed_buckets_about_the_cap(model, straddling, burst):
    buckets, calls = burst
    rows = straddling._admit_rows
    reqs = [(_prompt_in(k, b), 3 + k % 3, {}) for k, b in enumerate(buckets)]
    outs, delta = _burst(straddling, reqs)
    assert (delta["batches"], delta["admit_rows"]) == (1, len(reqs))
    assert delta["admit_steps"] == len(calls)
    assert delta["admit_rows"] == sum(n for _, n in calls)
    assert delta["admit_row_slots"] == sum(rows[b] for b, _ in calls)
    assert delta["admit_token_slots"] == sum(rows[b] * b for b, _ in calls)
    assert delta["admit_tokens"] == sum(len(p) for p, _, _ in reqs)
    for out, (p, budget, _) in zip(outs, reqs):
        assert out == _ref_greedy(model, p, budget)


def test_prefix_siblings_in_chunks_of_unequal_rows(model, straddling):
    sys_p = (np.arange(PAGE + 3) * 7 + 5) % 97  # one whole page and a tail
    kw = {"prefix_key": "sys16", "prefix_len": len(sys_p)}
    # a row of 8 between two siblings of 16: three calls, the siblings in
    # the first and the last
    reqs = [(np.concatenate([sys_p, _prompt(0)[:3]]), 4, kw),
            (_prompt_in(1, 8), 4, {}),
            (np.concatenate([sys_p, _prompt(2)[:2]]), 4, kw)]
    hits = straddling.stats()["prefix_hits"]
    outs, delta = _burst(straddling, reqs)
    assert (delta["admit_steps"], delta["admit_row_slots"]) == (3, 4)
    assert delta["admit_token_slots"] == 16 + 2 * 8 + 16
    assert straddling.stats()["prefix_hits"] == hits
    late = [(np.concatenate([sys_p, _prompt(9)[:2]]), 4, kw)]
    outs2, delta2 = _burst(straddling, late)
    assert straddling.stats()["prefix_hits"] == hits + 1
    # a request is routed by its whole prompt, so the late sibling's call
    # is its own bucket's one row, of which it prefills what follows the
    # prefix
    assert delta2["admit_token_slots"] == 16
    assert delta2["admit_tokens"] == len(late[0][0]) - len(sys_p)
    for out, (p, budget, _) in zip(outs + outs2, reqs + late):
        assert out == _ref_greedy(model, p, budget)


def test_each_buckets_program_has_its_own_rows(straddling):
    # after every burst above: nothing was compiled beyond the warm-up, and
    # the programs compiled_programs() lowers are the ones that ran
    assert straddling.metrics.snapshot()["admit_steps"] > 0
    assert straddling.compile_count == COMPILE_SET
    texts = straddling.compiled_programs()
    assert sorted(texts) == ["admit[16]", "admit[8]", "step"]
    assert "s32[2,8]" in texts["admit[8]"]
    assert "s32[1,8]" not in texts["admit[8]"]
    assert "s32[1,16]" in texts["admit[16]"]
    assert "s32[2,16]" not in texts["admit[16]"]
    assert straddling.compile_count == COMPILE_SET


def test_the_prefill_role_exports_rows_of_chunks_of_unequal_rows(model):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generation, "_ADMIT_TOKEN_SLOTS", BUCKETS[-1])
        pre = _engine(model, "straddle-pre", role="prefill")
        dec = _engine(model, "straddle-dec", role="decode")
    reqs = [(_prompt_in(k, b), 4, {"handoff": True})
            for k, b in enumerate([8, 16, 8, 8, 16])]
    with pre, dec:
        assert pre.warmup() == dec.warmup() == COMPILE_SET + 1
        with pre._batcher._cv:
            futs = [pre.submit(p, n, **kw) for p, n, kw in reqs]
        hands = [f.result(120) for f in futs]
        outs = [dec.submit(h.prompt, n, handoff=h).result(120).tolist()
                for h, (_, n, _) in zip(hands, reqs)]
        for _ in range(500):  # the counters trail the futures (see _burst)
            snap = pre.metrics.snapshot()
            if snap["evicted"] == len(reqs):
                break
            time.sleep(0.01)
        assert (snap["batches"], snap["admit_steps"],
                snap["admit_row_slots"]) == (1, 4, 6)
        assert pre.compile_count == dec.compile_count == COMPILE_SET + 1
    for out, (p, n, _) in zip(outs, reqs):
        assert out == _ref_greedy(model, p, n)


@pytest.mark.parametrize("n", BURSTS.values(), ids=BURSTS.keys())
def test_a_burst_of_n_is_served_the_greedy_reference(model, engine, n):
    reqs = [(_prompt(k), 3 + k % 3, {}) for k in range(n)]
    outs, delta = _burst(engine, reqs)
    _one_admitting_iteration(delta, reqs)
    for out, (p, budget, _) in zip(outs, reqs):
        assert out == _ref_greedy(model, p, budget)


def test_prefix_siblings_in_different_chunks(model, engine):
    sys_p = (np.arange(PAGE + 3) * 7 + 5) % 97  # one whole page and a tail
    kw = {"prefix_key": "sys", "prefix_len": len(sys_p)}
    reqs = [(np.concatenate([sys_p, _prompt(k)[:3]]), 4,
             kw if k in (0, R) else {}) for k in range(R + 1)]
    assert len(reqs[0][0]) <= BUCKETS[-1]
    hits = engine.stats()["prefix_hits"]
    outs, delta = _burst(engine, reqs)
    _one_admitting_iteration(delta, reqs)
    # neither sibling saw the other's pages: the prefix is registered only
    # once the last chunk has landed
    assert engine.stats()["prefix_hits"] == hits
    # a later sibling maps the registered pages (the boundary page copied
    # on write) and prefills only what follows the prefix
    late = [(np.concatenate([sys_p, _prompt(9)[:2]]), 4, kw)]
    outs2, delta2 = _burst(engine, late)
    assert engine.stats()["prefix_hits"] == hits + 1
    assert delta2["admit_tokens"] == len(late[0][0]) - len(sys_p)
    for out, (p, budget, _) in zip(outs + outs2, reqs + late):
        assert out == _ref_greedy(model, p, budget)


def test_the_compile_set_is_closed_at_buckets_plus_two(engine):
    # last of the plain engine's tests: every burst above has run, and the
    # count is still what warmup() returned
    assert engine.metrics.snapshot()["admit_steps"] > 0
    assert engine.compile_count == COMPILE_SET


def _alone_then_together(eng, reqs):
    alone = [_burst(eng, [r])[0][0] for r in reqs]
    together, delta = _burst(eng, reqs)
    _one_admitting_iteration(delta, reqs)
    return alone, together


def test_a_quantized_pool_follows_its_rows(model):
    reqs = [(_prompt(k), 4, {}) for k in range(B)]
    with _engine(model, "chunks-int8", quantized="int8") as eng:
        warmed = eng.warmup()
        alone, together = _alone_then_together(eng, reqs)
        assert eng.compile_count == warmed == COMPILE_SET
    # a row's mathematics depends on its own row alone
    assert together == alone


def test_adapter_ids_follow_the_chunks_rows():
    from paddle_tpu.lora import random_adapter

    lora = _gpt(lora_capacity=2, lora_rank=4)
    aids = [(0, -1, 1)[k % 3] for k in range(B)]
    reqs = [(_prompt(k), 5, {"adapter_id": a}) for k, a in enumerate(aids)]
    with _engine(lora, "chunks-lora") as eng:
        warmed = eng.warmup()
        for slot in (0, 1):
            eng.install_adapter(slot, random_adapter(
                lora, f"a{slot}", rank=4, seed=9 + slot, alpha=32.0, std=0.2))
        alone, together = _alone_then_together(eng, reqs)
        base = [_burst(eng, [(p, n, {})])[0][0] for p, n, _ in reqs]
        assert eng.compile_count == warmed == COMPILE_SET
    assert together == alone
    for out, b, (p, n, kw) in zip(together, base, reqs):
        if kw["adapter_id"] == -1:
            assert out == b == _ref_greedy(lora, p, n)
    # the adapters are strong enough to move a token somewhere, so a row
    # served with another row's adapter would have shown
    assert any(o != b for o, b, a in zip(together, base, aids) if a >= 0)


# -- a short last chunk waits for the row that is known to come ---------------
@pytest.mark.parametrize("steps,live,clock,pays", [
    # no reading of one side or the other: never
    (1, 4, dict(d_us=0, d=0, a_us=0, a=0), False),
    (1, 4, dict(d_us=900, d=3, a_us=0, a=0), False),
    (1, 4, dict(d_us=0, d=0, a_us=9000, a=3), False),
    # longgen_closed's readings (PERF.md): 14 ms steps, 63 live, 49.7 ms
    # calls: the break-even is 111 steps
    (10, 63, dict(d_us=14_000 * 50, d=50, a_us=49_700 * 9, a=9), True),
    (111, 63, dict(d_us=14_000 * 50, d=50, a_us=49_700 * 9, a=9), True),
    (112, 63, dict(d_us=14_000 * 50, d=50, a_us=49_700 * 9, a=9), False),
    # docs_closed's: 2.21 ms steps, 31 live, 11.7 ms calls: 82 steps
    (1, 31, dict(d_us=2_210 * 7, d=7, a_us=11_700 * 5, a=5), True),
    (83, 31, dict(d_us=2_210 * 7, d=7, a_us=11_700 * 5, a=5), False),
    # few slots, long answers: a step of one slot is no cheaper than the call
    (1, 1, dict(d_us=5_000, d=1, a_us=8_000, a=1), False),
    (37, 1, dict(d_us=20_000, d=1, a_us=200_000, a=1), False),
], ids=["no_reading", "no_call_yet", "no_step_yet", "qnx_10", "qnx_111",
        "qnx_112", "docs_1", "docs_83", "one_slot", "one_slot_long"])
def test_the_break_even_is_the_loops_own_step_against_half_its_call(
        steps, live, clock, pays):
    sums = {"loop_us_decode_device": clock["d_us"], "decode_steps": clock["d"],
            "loop_us_admit_device": clock["a_us"], "admit_steps": clock["a"]}
    assert generation.hold_pays(steps, live, sums) is pays


def test_the_loop_clock_keeps_what_the_rule_reads():
    from paddle_tpu.serving.metrics import LoopClock, ServingMetrics

    ph = LoopClock(ServingMetrics("kept", extra_counters=(
        *generation.LOOP_COUNTERS, *generation.SLOT_COUNTERS)))
    assert ph.sums == dict.fromkeys(
        ("loop_us_decode_device", "decode_steps", "loop_us_admit_device",
         "admit_steps"), 0)
    for _ in range(2):
        ph.to("admit.device")
        time.sleep(0.004)
        ph.to("decode.device")
        ph.counts.update(admit_steps=2, decode_steps=1)
        ph.flush()
    ph.to(None)
    assert (ph.sums["admit_steps"], ph.sums["decode_steps"]) == (4, 2)
    assert ph.sums["loop_us_admit_device"] >= 4000
    # a call of 2 ms against a step of microseconds: one step of one slot in
    # five pays, a million do not
    assert generation.hold_pays(1, 4, ph.sums)
    assert not generation.hold_pays(10 ** 6, 4, ph.sums)


@contextlib.contextmanager
def _paced(eng, admit_s=0.0, step_s=0.0, before_step=lambda: None):
    """Make the engine's own device calls last: what the loop's clock then
    reads is definite whatever the machine is doing."""
    padmit, step = eng._padmit, eng._step

    def slow_admit(*a):
        time.sleep(admit_s)
        return padmit(*a)

    def slow_step(*a):
        before_step()
        time.sleep(step_s)
        return step(*a)

    eng._padmit, eng._step = slow_admit, slow_step
    try:
        yield eng
    finally:
        eng._padmit, eng._step = padmit, step


def _primed(eng):
    """Both sides of the break-even have a reading: one request, served."""
    p = _prompt_in(0, BUCKETS[0])
    outs, delta = _burst(eng, [(p, 3, {})])
    assert delta["admit_steps"] == 1 and delta["admit_rows_held"] == 0
    return eng


@pytest.fixture(scope="module")
def holding(model):
    """Five slots whose admission calls last 50 ms against decode steps of a
    millisecond or two: a slot left empty for a few steps is cheap."""
    with _engine(model, "hold") as eng:
        assert eng.warmup() == COMPILE_SET
        with _paced(eng, admit_s=0.05):
            yield _primed(eng)


#: seven requests on five slots: slot 0 ends after two decode steps, slot 1
#: two steps later, the rest outlast the test's interest; two wait
HELD_BUDGETS = [3, 5, 12, 12, 12, 4, 4]


def _all_served_greedy(model, outs, reqs):
    for out, (p, budget, _) in zip(outs, reqs):
        assert out == _ref_greedy(model, p, budget)


def test_a_short_chunk_waits_for_the_next_freed_slots_row(model, holding):
    reqs = [(_prompt_in(k, BUCKETS[0]), n, {})
            for k, n in enumerate(HELD_BUDGETS)]
    outs, delta = _burst(holding, reqs)
    # five at once with nothing live (3 calls, the last short and not held),
    # then the sixth waits out slot 1's two steps and goes with the seventh
    assert delta["batches"] == 2
    assert (delta["admit_steps"], delta["admit_rows"],
            delta["admit_row_slots"]) == (4, 7, 4 * R)
    assert delta["admit_rows_held"] == 1  # once a row, not once an iteration
    assert delta["admit_hold_slot_steps"] == HELD_BUDGETS[1] - HELD_BUDGETS[0]
    assert delta["admit_token_slots"] == 4 * R * BUCKETS[0]
    _all_served_greedy(model, outs, reqs)


def test_mixed_buckets_pair_in_the_wider_rows_program(model, holding):
    # the held row is of the narrow bucket, its partner of the wide one
    widths = [BUCKETS[0]] * 5 + [BUCKETS[0], BUCKETS[1]]
    reqs = [(_prompt_in(k, b), n, {})
            for k, (b, n) in enumerate(zip(widths, HELD_BUDGETS))]
    outs, delta = _burst(holding, reqs)
    assert (delta["admit_steps"], delta["admit_rows_held"]) == (4, 1)
    assert delta["admit_token_slots"] == R * (3 * BUCKETS[0] + BUCKETS[1])
    _all_served_greedy(model, outs, reqs)


def test_never_held_with_an_empty_queue(model, holding):
    # the sixth request is the last: nobody is coming to fill its call
    reqs = [(_prompt_in(k, BUCKETS[0]), n, {})
            for k, n in enumerate(HELD_BUDGETS[:6])]
    outs, delta = _burst(holding, reqs)
    assert (delta["admit_steps"], delta["admit_rows"],
            delta["admit_row_slots"]) == (4, 6, 4 * R)
    assert (delta["admit_rows_held"], delta["admit_hold_slot_steps"]) == (0, 0)
    _all_served_greedy(model, outs, reqs)


def test_never_held_with_no_live_slot(model, holding):
    # answers of one token end at their admission: every slot is free in
    # every iteration and nothing is decoding whose end could be waited for
    reqs = [(_prompt_in(k, BUCKETS[0]), 1, {}) for k in range(11)]
    outs, delta = _burst(holding, reqs)
    assert (delta["batches"], delta["admit_steps"], delta["admit_rows"],
            delta["admit_row_slots"]) == (3, 7, 11, 7 * R)
    assert (delta["admit_rows_held"], delta["admit_hold_slot_steps"]) == (0, 0)
    _all_served_greedy(model, outs, reqs)


def test_the_hold_tests_compiled_nothing(holding):
    assert holding.metrics.snapshot()["admit_rows_held"] > 0
    assert holding.compile_count == COMPILE_SET


def test_never_held_where_the_bucket_has_one_row(model):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generation, "_ADMIT_TOKEN_SLOTS", BUCKETS[-1])
        eng = _engine(model, "hold-one-row")
    reqs = [(_prompt_in(k, BUCKETS[1]), n, {})
            for k, n in enumerate(HELD_BUDGETS)]
    with eng:
        assert eng._admit_rows == {8: 2, 16: 1}
        assert eng.warmup() == COMPILE_SET
        with _paced(eng, admit_s=0.05):
            _primed(eng)
            outs, delta = _burst(eng, reqs)
    # a call of the wide bucket is full with its one row
    assert (delta["admit_steps"], delta["admit_rows"],
            delta["admit_row_slots"]) == (7, 7, 7)
    assert (delta["admit_rows_held"], delta["admit_hold_slot_steps"]) == (0, 0)
    _all_served_greedy(model, outs, reqs)


def test_never_held_past_the_break_even(model):
    # two slots and a long answer: the one live slot would decode alone for
    # 21 steps of 20 ms to save half a call of a few
    reqs = [(_prompt_in(k, BUCKETS[0]), n, {})
            for k, n in enumerate([3, 24, 4, 4])]
    with GenerationEngine(model, prompt_buckets=BUCKETS, batch_size=2,
                          cache_len=CACHE, kv_page_size=PAGE,
                          speculative_k=0, name="hold-two-slots") as eng:
        assert eng._admit_rows == {b: 2 for b in BUCKETS}
        assert eng.warmup() == COMPILE_SET
        with _paced(eng, step_s=0.02):
            _primed(eng)
            outs, delta = _burst(eng, reqs)
        assert eng.compile_count == COMPILE_SET
    # the third goes alone as soon as slot 0 ends, though the fourth waits
    assert (delta["admit_steps"], delta["admit_rows"],
            delta["admit_row_slots"]) == (3, 4, 3 * R)
    assert (delta["admit_rows_held"], delta["admit_hold_slot_steps"]) == (0, 0)
    _all_served_greedy(model, outs, reqs)


def test_never_held_while_closing(model):
    reqs = [(_prompt_in(k, BUCKETS[0]), n, {})
            for k, n in enumerate(HELD_BUDGETS)]
    eng = _engine(model, "hold-closing")
    assert eng.warmup() == COMPILE_SET
    with _paced(eng, admit_s=0.05):
        _primed(eng)
        before = eng.metrics.snapshot()
        with eng._batcher._cv:
            futs = [eng.submit(p, n) for p, n, _ in reqs]
        # the first iteration's three calls last 150 ms: the engine is
        # closing long before slot 0 ends
        eng.close(drain=True, timeout=120)
    outs = [f.result(1).tolist() for f in futs]
    snap = eng.metrics.snapshot()
    delta = {k: snap[k] - before[k] for k in (
        "admit_steps", "admit_rows", "admit_rows_held",
        "admit_hold_slot_steps")}
    assert delta == {"admit_steps": 5, "admit_rows": 7, "admit_rows_held": 0,
                     "admit_hold_slot_steps": 0}
    _all_served_greedy(model, outs, reqs)


def test_a_hand_off_adoption_is_never_held(model):
    reqs = [(_prompt_in(k, BUCKETS[0]), n, {"handoff": True})
            for k, n in enumerate(HELD_BUDGETS)]
    with _engine(model, "hold-pre", role="prefill") as pre, \
            _engine(model, "hold-dec", role="decode") as dec:
        assert pre.warmup() == dec.warmup() == COMPILE_SET + 1
        hands = [pre.submit(p, n, **kw).result(120) for p, n, kw in reqs]
        with _paced(dec, admit_s=0.05):
            outs, delta = _burst(dec, [
                (h.prompt, n, {"handoff": h})
                for h, (_, n, _) in zip(hands, reqs)])
    # adopted as slots free, one by one, with a request waiting behind them
    assert delta["handoffs_in"] == len(reqs)
    assert (delta["admit_steps"], delta["admit_rows_held"],
            delta["admit_hold_slot_steps"]) == (0, 0, 0)
    _all_served_greedy(model, outs, reqs)


def test_a_held_request_still_expires_by_its_deadline(model):
    from paddle_tpu.framework.errors import ExecutionTimeoutError

    budgets = [3, 22, 22, 22, 22, 4, 4]
    deadline_s = 2.0
    reqs = [(_prompt_in(k, BUCKETS[0]), n,
             {"deadline_ms": deadline_s * 1e3} if k == 5 else {})
            for k, n in enumerate(budgets)]
    with _engine(model, "hold-deadline") as eng:
        assert eng.warmup() == COMPILE_SET
        state = {}

        def outlast_the_deadline():
            # the loop's thread, about to dispatch a step: once the sixth
            # request is held, stand still until its deadline has passed
            if ("t0" in state and "stood" not in state
                    and eng.metrics.snapshot()["admit_rows_held"]
                    > state["held"]):
                state["stood"] = True
                time.sleep(max(0.0, state["t0"] + deadline_s
                               - time.monotonic()) + 0.05)

        with _paced(eng, admit_s=0.2, before_step=outlast_the_deadline):
            _primed(eng)
            before = eng.metrics.snapshot()
            state["held"] = before["admit_rows_held"]
            with eng._batcher._cv:
                state["t0"] = time.monotonic()
                futs = [eng.submit(p, n, **kw) for p, n, kw in reqs]
            with pytest.raises(ExecutionTimeoutError):
                futs[5].result(120)
            outs = [f.result(120).tolist()
                    for k, f in enumerate(futs) if k != 5]
            for _ in range(500):
                snap = eng.metrics.snapshot()
                if snap["evicted"] - before["evicted"] >= len(reqs) - 1:
                    break
                time.sleep(0.01)
    assert state.get("stood")
    assert snap["expired"] - before["expired"] == 1
    # it was held, and it expired where it waited; the seventh, with nobody
    # behind it, went alone
    assert snap["admit_rows_held"] - before["admit_rows_held"] == 1
    assert snap["admit_rows"] - before["admit_rows"] == len(reqs) - 1
    _all_served_greedy(model, outs, [r for k, r in enumerate(reqs) if k != 5])
