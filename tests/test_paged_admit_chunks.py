"""The paged admission prefill runs over a fixed row chunk
(serving/generation.py ``_ADMIT_ROWS``): an iteration that admits n requests
dispatches ``ceil(n / R)`` programs of ``[R, bucket]``, each over its own
rows' page-table rows.  Served tokens stay the uncached greedy reference's
whatever n is, siblings of one prefix may fall in different chunks, a
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
                "batches", "admit_steps", "admit_rows", "admit_tokens",
                "admit_token_slots")}
        time.sleep(0.01)
    raise AssertionError("the loop never flushed its last iteration")


def _one_admitting_iteration(delta, requests):
    n = len(requests)
    assert (delta["batches"], delta["admit_rows"]) == (1, n)
    assert delta["admit_steps"] == -(-n // R)
    assert delta["admit_tokens"] == sum(len(p) for p, _, _ in requests)
    # every chunk is R rows of a bucket
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
    assert engine._admit_rows == R < B
    with GenerationEngine(model, prompt_buckets=[8], batch_size=1,
                          cache_len=CACHE, kv_page_size=PAGE,
                          speculative_k=0, name="chunks-b1") as one:
        assert one._admit_rows == 1


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
