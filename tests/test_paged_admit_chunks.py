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
"""
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
                "admit_tokens", "admit_token_slots")}
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
