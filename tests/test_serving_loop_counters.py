"""The paged decode loop on the record (serving/generation.py
``_paged_loop``, serving/metrics.py ``LOOP_COUNTERS``/``LoopClock``): the
``loop_us_*`` phases tile the loop's time and ``loop_max_us_*`` keep each
phase's longest interval, the work counters count what was dispatched, the
summed request times bracket what the caller saw, and in a profiler trace
the fixed ``serve/*`` span names lie on the engine's thread.  Served tokens
stay the uncached greedy reference's.
"""
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.generation import admit_rows
from paddle_tpu.serving.metrics import (LOOP_COUNTERS, LOOP_PHASES, LoopClock,
                                        ServingMetrics)

B, BUCKET, PAGE, CACHE = 2, 16, 8, 64
R = admit_rows(BUCKET, B)  # rows of the bucket's admission program
PROMPTS = [(np.arange(10) * 5 + 2) % 97, np.arange(3) % 97,
           (np.arange(6) * 3) % 97, (np.arange(4) * 7 + 1) % 97,
           (np.arange(12) * 11 + 3) % 97]
BUDGETS = [14, 3, 4, 5, 3]
PHASE_KEYS = [k for k in LOOP_COUNTERS
              if k.startswith("loop_us_") and k != "loop_us_total"]
CLOCK_KEYS = tuple(k for k in LOOP_COUNTERS if k.startswith("loop_"))


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    pt.seed(4321)
    m = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                                 num_heads=4, max_position=CACHE,
                                 dropout=0.0))
    m.eval()
    return m


def _ref_greedy(model, prompt, n):
    import jax.numpy as jnp

    # the existing paged tests' uncached forward, at ONE padded shape: the
    # model is causal, so what follows position len(ids) - 1 cannot move it
    ids, outs = list(map(int, prompt)), []
    for _ in range(n):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(ids)] = ids
        logits = np.asarray(model(jnp.asarray(padded)))[0]
        outs.append(int(np.argmax(logits[len(ids) - 1])))
        ids.append(outs[-1])
    return outs


def _settled(eng, n_evicted):
    """The engine's snapshot once the loop has flushed the iteration that
    finished the last request (counters trail a resolved future by the
    rest of its iteration)."""
    for _ in range(500):
        snap = eng.metrics.snapshot()
        if snap["evicted"] >= n_evicted:
            return snap
        time.sleep(0.01)
    raise AssertionError("the loop never flushed its last iteration")


@pytest.fixture(scope="module")
def engine(model):
    with GenerationEngine(model, prompt_buckets=[BUCKET], batch_size=B,
                          cache_len=CACHE, kv_page_size=PAGE,
                          speculative_k=0, name="loopctr") as eng:
        yield eng


@pytest.fixture(scope="module")
def served(engine):
    """One staggered run, no shared prefix: more requests than slots, so
    admissions fall between decode steps."""
    before = engine.metrics.snapshot()
    engine.warmup()
    sent, done = [], {}

    def submit(k):
        sent.append(time.monotonic())
        f = engine.submit(PROMPTS[k], BUDGETS[k])
        f.add_done_callback(lambda _: done.setdefault(k, time.monotonic()))
        return f

    futs = [submit(0), submit(1)]
    for k in range(2, len(PROMPTS)):
        time.sleep(0.02)
        futs.append(submit(k))
    outs = [f.result(120) for f in futs]
    return {"before": before, "snap": _settled(engine, len(futs)),
            "latency_us": sum((done[k] - t) * 1e6
                              for k, t in enumerate(sent)),
            "outs": outs}


def test_every_loop_counter_is_a_zero_int_before_the_first_request(served):
    for k in LOOP_COUNTERS:
        assert type(served["before"][k]) is int, k
    assert all(served["before"][k] == 0 for k in LOOP_COUNTERS
               if k not in CLOCK_KEYS)
    assert all(type(served["snap"][k]) is int for k in LOOP_COUNTERS)


def test_phases_sum_to_the_total_within_one_percent(served):
    s = served["snap"]
    assert s["loop_us_total"] > 0
    assert abs(sum(s[k] for k in PHASE_KEYS) - s["loop_us_total"]) \
        <= 0.01 * s["loop_us_total"]
    for k in ("loop_us_admit_device", "loop_us_decode_device",
              "loop_us_harvest", "loop_us_wait"):
        assert s[k] > 0, k


def test_served_tokens_are_the_uncached_greedy_reference(model, served):
    for out, p, b in zip(served["outs"], PROMPTS, BUDGETS):
        assert out.tolist() == _ref_greedy(model, p, b)


def test_a_phases_longest_interval_lies_between_its_mean_and_its_sum(served):
    s = served["snap"]
    # one interval an admitting iteration: all of its chunks' calls are
    # dispatched inside the one admit.device phase; a decode step opens
    # one at its dispatch, and one more where it is read on its own (behind
    # an admission's dispatch, or with nothing left to run ahead of it)
    intervals = {"admit_device": s["batches"],
                 "decode_device": 2 * s["decode_steps"]}
    for k in PHASE_KEYS:
        longest = s[k.replace("loop_us_", "loop_max_us_")]
        assert 0 <= longest <= s[k], k
        n = intervals.get(k[len("loop_us_"):])
        if n:  # the longest interval is no shorter than the mean
            assert longest >= s[k] // n, k


def test_steps_ahead_are_some_of_the_steps_and_no_token_is_stale(served):
    s = served["snap"]
    # requests of 3 to 14 tokens on two slots: pure decode iterations run
    # ahead, the step behind an admission does not; no EOS, so no step
    # computed a token past a request's end
    assert 0 < s["decode_steps_ahead"] < s["decode_steps"]
    assert s["decode_tokens_stale"] == 0


def test_live_slot_steps_is_bounded_by_the_slots(served):
    s = served["snap"]
    assert 0 < s["decode_steps"] <= s["live_slot_steps"] \
        <= B * s["decode_steps"]
    # every token but a request's first comes out of a decode step
    assert s["live_slot_steps"] == sum(BUDGETS) - len(BUDGETS)


def test_admission_counts_rows_tokens_and_token_slots(served):
    s = served["snap"]
    assert s["admit_rows"] == s["admitted"] == len(PROMPTS)
    assert s["admit_tokens"] == sum(len(p) for p in PROMPTS)
    # an iteration that admits n rows dispatches ceil(n / R) programs of
    # [R(bucket), bucket], and is one batch
    assert 2 <= s["batches"] <= s["admit_steps"] <= len(PROMPTS)
    assert -(-s["admit_rows"] // R) <= s["admit_steps"] \
        <= -(-B // R) * s["batches"]
    assert s["admit_row_slots"] == R * s["admit_steps"]
    assert s["admit_token_slots"] == R * BUCKET * s["admit_steps"]


def test_live_pages_are_counted_against_the_page_table(served):
    s = served["snap"]
    assert s["kv_page_slots_steps"] == B * (CACHE // PAGE) * s["decode_steps"]
    assert 0 < s["kv_pages_live_steps"] <= s["kv_page_slots_steps"]
    # a live slot maps at least one page
    assert s["kv_pages_live_steps"] >= s["live_slot_steps"]


def test_swept_pages_are_the_bounds_the_step_program_was_given():
    # kv_pages_swept_steps: per decode step, the pages inside the slots'
    # sweep bounds (ops/paged_attention.py: whole key blocks up to the
    # last page with a visible key), summed.  Here a window of 4 blocks of
    # 2 pages: a short request sweeps one block, the long one grows into
    # its third, a free slot none.  Computed again from the very rows the
    # step program was handed
    from paddle_tpu.ops.paged_attention import (block_pages, key_visible,
                                                sweep_bound)

    page, cache, slots = 64, 512, 3
    G, ppb = cache // page, block_pages(page)
    assert (G, ppb) == (8, 2)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    pt.seed(99)
    wide = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32,
                                    num_layers=1, num_heads=4,
                                    max_position=cache, dropout=0.0))
    wide.eval()
    seen = []
    with GenerationEngine(wide, prompt_buckets=[16, 288], batch_size=slots,
                          cache_len=cache, kv_page_size=page,
                          speculative_k=0, name="sweptctr") as eng:
        eng.warmup()
        step = eng._step

        def spy(params, buffers, packed, prev, pool):
            seen.append(np.array(packed))
            return step(params, buffers, packed, prev, pool)

        eng._step = spy
        before = eng.metrics.snapshot()
        futs = [eng.submit(np.arange(250) % 97, 12),
                eng.submit(np.arange(5) % 97, 6)]
        for f in futs:
            f.result(120)
        after = _settled(eng, before["evicted"] + len(futs))
    d = {k: after[k] - before[k]
         for k in (*LOOP_COUNTERS, "decode_steps")}
    given, per_slot, steps = 0, set(), 0
    for packed in seen:
        T = (packed.shape[1] - cache - G) // 2
        pos, pm = packed[:, T:2 * T], packed[:, 2 * T:2 * T + cache]
        steps += bool((pos >= 0).any())  # not the pool's inert first call
        pages = sweep_bound(
            key_visible(pm[:, None, :], pos[:, :, None], cache), page)
        given += int(pages.sum())
        per_slot.update(pages.tolist())
    assert steps == d["decode_steps"] > 0
    assert d["kv_pages_swept_steps"] == given
    assert per_slot == {0, 2, 4, 6}  # free; short; 250 + 12 crosses 256
    assert d["kv_pages_swept_steps"] <= d["kv_page_slots_steps"] \
        == slots * G * d["decode_steps"]
    # whole blocks up to the newest written page: at most the page a slot
    # has mapped ahead of its next write is outside
    assert d["kv_pages_swept_steps"] >= (d["kv_pages_live_steps"]
                                         - d["live_slot_steps"])
    assert d["kv_pages_swept_steps"] < d["kv_page_slots_steps"]


def test_admission_blocks_walked_against_a_hand_count():
    # admit_attn_blocks_walked / _square: per admission call, the (query
    # tile, key block) pairs the paged_decode kernel walks, each tile to
    # its own bound, beside the tiles of the call x each slot's bound (what
    # it walked while a grid step held the bucket whole).  One call of two
    # rows in the 384 bucket (two tiles of 192 rows), pages of 16, key
    # blocks of 128:
    #   a cold row of 300 tokens: its tiles end at keys 191 and 299:
    #     2 + 3 blocks walked, 2 tiles x 3 blocks square;
    #   a row of 100 tokens behind a shared prefix of 160 (positions 160 ..
    #     259, all in the first tile, which reads the prefix's blocks too):
    #     3 + 0 walked, 2 x 3 square
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.ops.paged_attention import block_pages, query_tile
    from paddle_tpu.serving.generation import attn_blocks

    page, cache, bucket = 16, 512, 384
    assert (query_tile(bucket), block_pages(page) * page) == (192, 128)
    pt.seed(7)
    m = GPTForCausalLM(GPTConfig(vocab_size=97, hidden_size=32, num_layers=1,
                                 num_heads=4, max_position=cache,
                                 dropout=0.0))
    m.eval()
    sys_p = (np.arange(160) * 7 + 5) % 97
    kw = {"prefix_key": "sys", "prefix_len": len(sys_p)}
    keys = ("admit_attn_blocks_walked", "admit_attn_blocks_square",
            "admit_steps", "evicted")
    given = []
    with GenerationEngine(m, prompt_buckets=[bucket], batch_size=3,
                          cache_len=cache, kv_page_size=page,
                          speculative_k=0, name="walkctr") as eng:
        assert eng._admit_rows == {bucket: 2}
        eng.warmup()
        warm = eng.metrics.snapshot()
        # warm-up's rows are inert: nothing to walk
        assert warm["admit_attn_blocks_walked"] == 0
        assert warm["admit_attn_blocks_square"] == 0
        first = np.concatenate([sys_p, np.arange(5) % 97])
        eng.submit(first, 2, **kw).result(120)
        before = _settled(eng, warm["evicted"] + 1)
        # 165 tokens, all in the first tile: 2 + 0 of 2 x 2
        assert before["admit_attn_blocks_walked"] == 2
        assert before["admit_attn_blocks_square"] == 4
        admit = eng._padmit

        def spy(params, buffers, ids, pp, pm, *rest):
            given.append((np.asarray(pm), np.asarray(pp)))
            return admit(params, buffers, ids, pp, pm, *rest)

        eng._padmit = spy
        hits = eng.stats()["prefix_hits"]
        with eng._batcher._cv:  # one admitting iteration takes both
            futs = [eng.submit((np.arange(300) * 3 + 1) % 97, 2),
                    eng.submit(np.concatenate(
                        [sys_p, (np.arange(100) * 11) % 97]), 2, **kw)]
        for f in futs:
            f.result(120)
        after = _settled(eng, before["evicted"] + 2)
        assert eng.stats()["prefix_hits"] == hits + 1
    d = {k: after[k] - before[k] for k in keys}
    assert d["admit_steps"] == 1 and len(given) == 1
    pm, pp = given[0]
    assert pp[0, :300].tolist() == list(range(300)) and pp[0, 300] == -1
    assert pp[1, :100].tolist() == list(range(160, 260)) and pp[1, 100] == -1
    assert attn_blocks(pm, pp, page) == (8, 12)
    assert (d["admit_attn_blocks_walked"],
            d["admit_attn_blocks_square"]) == (8, 12)


def test_walked_blocks_never_pass_the_square(served):
    # a bucket of one tile: the two are the same count, and not zero
    s = served["snap"]
    assert 0 < s["admit_attn_blocks_walked"] == s["admit_attn_blocks_square"]


def test_request_times_are_ordered_and_inside_what_the_caller_saw(served):
    s = served["snap"]
    # a request waits, is prefilled, and only then can complete: summed
    # over the requests, queue wait < time to first token <= latency
    assert 0 < s["queue_wait_us"] < s["ttft_us"] <= served["latency_us"]
    # the prefill call lies between admission and the first token
    assert s["ttft_us"] - s["queue_wait_us"] >= s["loop_us_admit_device"]


def test_a_failed_dispatch_counts_no_work(model):
    from paddle_tpu.resilience.faults import FaultPlan

    with GenerationEngine(model, prompt_buckets=[BUCKET], batch_size=B,
                          cache_len=CACHE, kv_page_size=PAGE,
                          speculative_k=0, circuit_breaker=False,
                          name="loopctr-fault") as eng:
        eng.warmup()
        with FaultPlan.parse(
                "site=serving.decode,nth=1,error=TransientDeviceError"):
            out = eng.submit(PROMPTS[1], 3).result(120)
        s = _settled(eng, 1)
    assert out.tolist() == _ref_greedy(model, PROMPTS[1], 3)
    # the admission that failed before its dispatch left nothing behind:
    # one row, its prompt's tokens and one wait are counted, once
    assert s["restarts"] == 1
    assert (s["admit_rows"], s["admit_steps"]) == (1, 1)
    assert s["admit_tokens"] == len(PROMPTS[1])
    assert s["live_slot_steps"] == s["decode_steps"] == 2


def test_span_names_are_fixed_and_lie_on_the_engines_thread(
        engine, served, tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test/main_thread"):
            futs = [engine.submit(p, 4) for p in PROMPTS[:3]]
            for f in futs:
                f.result(120)
            time.sleep(0.12)  # an idle iteration: serve/wait, serve/publish
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = {}  # line index -> names on it
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve/", "test/", engine.name)):
                    lines.setdefault(n, set()).add(e.name)
    on = [n for n, names in lines.items()
          if any(x.startswith("serve/") for x in names)]
    assert len(on) == 1, lines
    assert lines[on[0]] == {"serve/" + p for p in LOOP_PHASES}
    assert all("test/main_thread" not in lines[n] for n in on)
    assert any("test/main_thread" in names for names in lines.values())


def test_add_advances_many_counters_under_one_call():
    m = ServingMetrics("m", extra_counters=("a",))
    m.add({"a": 2, "b": 3})
    m.add({"a": 5})
    snap = m.snapshot()
    assert (snap["a"], snap["b"]) == (7, 3)
    assert type(snap["a"]) is int


def test_loop_clock_tiles_time_and_carries_remainders():
    m = ServingMetrics("m")
    ph = LoopClock(m)
    ph.counts["work"] += 1
    for _ in range(50):
        ph.to("sched")
        ph.to("decode.device", live=1)
        assert ph.to("harvest") >= 0
        ph.flush()
    assert not ph.counts  # handed over
    ph.to(None)
    ph.flush()
    s = m.snapshot()
    phases = sum(s.get("loop_us_" + p.replace(".", "_"), 0)
                 for p in LOOP_PHASES)
    # fifty iterations of a few microseconds: only carried remainders keep
    # the integer sums within a microsecond a phase of the clock
    assert abs(phases - s["loop_us_total"]) <= len(LOOP_PHASES)
    assert s["loop_us_total"] > 0 and s["work"] == 1


def test_loop_clock_keeps_each_phases_longest_interval_across_flushes():
    m = ServingMetrics("m")
    ph = LoopClock(m)
    ph.to("decode.device")
    time.sleep(0.002)
    ph.to("wait")
    time.sleep(0.02)
    ph.flush()  # a flush does not cut the open interval
    time.sleep(0.02)
    ph.to("decode.device")  # a shorter second interval: the record stands
    ph.to(None)
    ph.flush()
    s = m.snapshot()
    assert s["loop_max_us_wait"] >= 40_000
    assert s["loop_max_us_wait"] == s["loop_us_wait"]
    assert 2_000 <= s["loop_max_us_decode_device"] \
        <= s["loop_us_decode_device"]
