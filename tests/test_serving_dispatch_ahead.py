"""One decode step in flight (``serving/generation.py:_paged_loop``): the
loop dispatches step n+1 before it reads step n's tokens.  Served tokens
are those of the SERIALIZED loop, which :func:`serial_reference` is: one
request alone, through the engine's own executables, every token read on
the host before the next step is packed, nothing taken from ``prev``.  The
three model families (GPT, the latent-attention decoder with routed
experts, the hybrid decoder with slot state), a budget's end by count, an
EOS seen one step late, and what has to read the step in flight before it
touches a slot: a preemption, an injected fault, ``close()``, speculation.
"""
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import loader  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.framework.errors import UnavailableError  # noqa: E402
from paddle_tpu.resilience.faults import FaultPlan  # noqa: E402
from paddle_tpu.serving import GenerationEngine  # noqa: E402
from paddle_tpu.serving.paging import PagePool  # noqa: E402

VOCAB, CACHE, PAGE, BUCKETS = 512, 128, 8, [16, 32]


def _gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    pt.seed(1234)
    return GPTForCausalLM(GPTConfig(vocab_size=VOCAB, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_position=CACHE, dropout=0.0))


def _latent():
    fam = loader.load_module("families", "joyai_flash")
    cfg = dict(first_k_dense_replace=1, n_shared_experts=1,
               norm_topk_prob=True, routed_scaling_factor=2.5,
               rms_norm_eps=1e-6, rope_theta=32000000,
               param_dtype="float32", serve={"cache_len": CACHE}, **fam.TINY)
    return fam.build_model(cfg, fam.make_weights(cfg, 5))


def _hybrid():
    fam = loader.load_module("families", "olmo_hybrid")
    cfg = dict(param_dtype="float32", linear_conv_kernel_dim=4,
               linear_allow_neg_eigval=True,
               rope_parameters={"rope_theta": None},
               serve={"cache_len": CACHE}, **fam.TINY)
    return fam.build_model(cfg, fam.make_weights(cfg, 5))


@pytest.fixture(scope="module", params=["gpt", "latent", "hybrid"])
def family(request):
    m = {"gpt": _gpt, "latent": _latent, "hybrid": _hybrid}[request.param]()
    m.eval()
    return request.param, m


@pytest.fixture(scope="module")
def gpt():
    m = _gpt()
    m.eval()
    return m


def engine(m, batch=4, **kw):
    kw = {"prompt_buckets": BUCKETS, "kv_page_size": PAGE, "cache_len": CACHE,
          "speculative_k": 0, "eos_token_id": None, "name": "ahead", **kw}
    return GenerationEngine(m, batch_size=batch, **kw)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).astype(np.int32) for n in lengths]


def serial_reference(eng, prompt, n):
    """``n`` greedy tokens for ``prompt`` in slot 0 of an empty pool, by the
    engine's admission and step programs called one at a time."""
    B, C, page = eng._batch, eng._C, eng._page
    G = C // page
    pool = PagePool(B, eng._kv_pages, page, C)
    _, cache = eng._init_pool()
    sb = next(b for b in eng._buckets if len(prompt) <= b)
    R, L = eng._admit_rows[sb], len(prompt)
    pool.admit(0, prompt)
    ids, pp = np.zeros((R, sb), np.int32), np.full((R, sb), -1, np.int32)
    pm, tb = np.full((R, C), -1, np.int32), np.full((R, G), -1, np.int32)
    lens, rows = np.ones((R,), np.int32), np.full((R,), -1, np.int32)
    ids[0, :L], pp[0, :L], lens[0], rows[0] = prompt, np.arange(L), L, 0
    pm[0], tb[0] = pool.pos_map[0], pool.table[0]
    first, cache = eng._padmit(
        eng._params, eng._buffers, jnp.asarray(ids), jnp.asarray(pp),
        jnp.asarray(pm), jnp.asarray(tb), jnp.asarray(lens), cache,
        eng._aids_arg(np.full((R,), -1, np.int32)), eng._slots_arg(rows))
    out = [int(np.asarray(first)[0])]
    for p in range(L, L + n - 1):
        assert pool.ensure_writable(0, p) is None
        pool.pos_map[0, p % C] = p
        ids1, pp1 = np.zeros((B, 1), np.int32), np.full((B, 1), -1, np.int32)
        ids1[0, 0], pp1[0, 0] = out[-1], p
        tok, cache = eng._step(
            eng._params, eng._buffers,
            eng._pack_step(ids1, pp1, pool.pos_map, pool.table),
            eng._no_prev(), cache)
        out.append(int(np.asarray(tok)[0, 0]))
    return out


def settled(eng, n_evicted, timeout=10.0, **at_least):
    """The engine's stats once the loop has flushed the iteration that
    ended the last request (the counters trail a resolved future), and
    any further counter has reached its value."""
    at_least["evicted"] = n_evicted
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        st = eng.stats()
        if all(st[k] >= v for k, v in at_least.items()):
            return st
        time.sleep(0.01)
    raise AssertionError(f"the loop never reached {at_least}: {st}")


def no_page_is_lost(st, eng):
    assert st["kv_pages_leaked"] == 0
    assert st["kv_pages_free"] == eng._kv_pages


# -- tokens --------------------------------------------------------------------
def test_served_tokens_are_the_serialized_loops(family):
    """More requests than slots, budgets from 1 up: slots end by count and
    are seated again while the step that ends them is still unread."""
    name, m = family
    prompts = prompts_of((5, 16, 20, 31, 9, 12, 2, 1, 17, 3), seed=3)
    budgets = [12, 1, 7, 2, 9, 3, 14, 5, 2, 8]
    eng = engine(m, name="ahead-" + name)
    try:
        warm = eng.warmup()
        futures = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = [np.asarray(f.result(timeout=300)).tolist() for f in futures]
        st = settled(eng, len(prompts))
        assert eng.compile_count == warm
        want = [serial_reference(eng, p, b) for p, b in zip(prompts, budgets)]
    finally:
        eng.close()
    assert outs == want
    assert [len(o) for o in outs] == budgets
    assert 0 < st["decode_steps_ahead"] <= st["decode_steps"]
    assert st["decode_tokens_stale"] == 0
    # every token but a request's first is one live row of one step: no
    # slot stood empty with its request unfinished, none ran past its end
    assert st["live_slot_steps"] == sum(budgets) - len(budgets)
    no_page_is_lost(st, eng)


@pytest.mark.parametrize("budget", [1, 2, 3])
def test_a_short_budget_ends_by_count(gpt, budget):
    (prompt,) = prompts_of((11,), seed=budget)
    eng = engine(gpt, batch=2, name=f"ahead-budget{budget}")
    try:
        eng.warmup()
        out = np.asarray(eng.submit(prompt, budget).result(120)).tolist()
        st = settled(eng, 1)
        want = serial_reference(eng, prompt, budget)
    finally:
        eng.close()
    assert out == want and len(out) == budget
    assert st["decode_steps"] == st["live_slot_steps"] == budget - 1
    # alone in the engine: every step after the first is dispatched with
    # the one before it unread
    assert st["decode_steps_ahead"] == max(budget - 2, 0)
    no_page_is_lost(st, eng)


@pytest.mark.parametrize("which", ["gpt", "hybrid"])
def test_an_eos_mid_answer_drops_the_one_token_computed_past_it(which):
    m = {"gpt": _gpt, "hybrid": _hybrid}[which]()
    m.eval()
    (prompt,) = prompts_of((13,), seed=7)
    plain = engine(m, batch=2, name="ahead-noeos")
    try:
        plain.warmup()
        full = serial_reference(plain, prompt, 20)
    finally:
        plain.close()
    # the first token of the answer, from its fourth on, that no earlier
    # one equals: the EOS fires there, in a decode step, mid-answer
    k = next(j for j in range(3, 20) if full[j] not in full[:j])
    eng = engine(m, batch=2, eos_token_id=full[k], name="ahead-eos")
    try:
        eng.warmup()
        out = np.asarray(eng.submit(prompt, 20).result(120)).tolist()
        # the stale row is counted an iteration after the future resolved
        st = settled(eng, 1, decode_tokens_stale=1)
    finally:
        eng.close()
    assert out == full[:k + 1]
    # step k+1 was in flight when step k's EOS was read: one token past
    # the end, dropped; the request's pages went back then
    assert st["decode_tokens_stale"] == 1
    assert st["decode_steps"] == st["live_slot_steps"] == k + 1
    assert st["tokens"] == k + 1
    no_page_is_lost(st, eng)


# -- what reads the step in flight first ---------------------------------------
def test_a_pool_too_small_preempts_with_a_step_in_flight(gpt):
    pa, pb = prompts_of((4, 4), seed=11)
    eng = engine(gpt, batch=2, prompt_buckets=[8], cache_len=32,
                 kv_page_size=4, kv_pages=9, circuit_breaker=False,
                 name="ahead-preempt")
    try:
        eng.warmup()
        fa, fb = eng.submit(pa, 26), eng.submit(pb, 26)
        outs = [np.asarray(f.result(120)).tolist() for f in (fa, fb)]
        st = settled(eng, 2)
        want = [serial_reference(eng, p, 26) for p in (pa, pb)]
    finally:
        eng.close()
    assert outs == want
    assert st["preempted"] >= 1 and st["decode_steps_ahead"] > 0
    assert st["decode_tokens_stale"] == 0
    no_page_is_lost(st, eng)


@pytest.mark.parametrize("nth", [3, 5, 8])
def test_a_fault_with_a_step_in_flight_requeues_its_rows_too(gpt, nth):
    """``serving.decode`` is passed once an admitting iteration and once a
    decode step: the nth passage fails with earlier steps unread, among
    them (budget 2, 3) rows whose slots were freed by count."""
    prompts = prompts_of((9, 14, 3, 20, 6), seed=5)
    budgets = [10, 2, 3, 8, 2]
    eng = engine(gpt, batch=4, circuit_breaker=False, name=f"ahead-f{nth}")
    try:
        eng.warmup()
        with FaultPlan.parse(
                f"site=serving.decode,nth={nth},error=TransientDeviceError"):
            futures = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
            outs = [np.asarray(f.result(120)).tolist() for f in futures]
        st = settled(eng, len(prompts))
        want = [serial_reference(eng, p, b) for p, b in zip(prompts, budgets)]
    finally:
        eng.close()
    assert outs == want
    assert st["restarts"] == 1 and st["errors"] == 0
    assert st["completed"] == len(prompts)
    no_page_is_lost(st, eng)


def test_close_draining_answers_every_request_in_flight(gpt):
    prompts = prompts_of((9, 14, 3, 20, 6, 11), seed=6)
    eng = engine(gpt, batch=4, name="ahead-drain")
    eng.warmup()
    futures = [eng.submit(p, 9) for p in prompts]
    while eng.metrics.snapshot()["decode_steps_ahead"] < 2:
        time.sleep(0.001)  # a step is unread from here on
    eng.close(drain=True, timeout=120)
    assert not eng._thread.is_alive()
    outs = [np.asarray(f.result(0)).tolist() for f in futures]
    ref = engine(gpt, batch=4, name="ahead-drain-ref")
    try:
        assert outs == [serial_reference(ref, p, 9) for p in prompts]
    finally:
        ref.close()
    assert eng.stats()["kv_pages_leaked"] == 0


def test_close_without_draining_resolves_every_future(gpt):
    prompts = prompts_of((9, 14, 3, 20, 6, 11), seed=8)
    eng = engine(gpt, batch=2, name="ahead-drop")
    eng.warmup()
    futures = [eng.submit(p, 40) for p in prompts]
    while eng.metrics.snapshot()["decode_steps_ahead"] < 2:
        time.sleep(0.001)
    eng.close(drain=False, timeout=120)
    assert not eng._thread.is_alive()
    ref = engine(gpt, batch=2, name="ahead-drop-ref")
    try:
        for f, p in zip(futures, prompts):
            assert f.done()
            if f.exception() is None:  # ended by the step that was unread
                assert np.asarray(f.result()).tolist() == \
                    serial_reference(ref, p, 40)
            else:
                assert isinstance(f.exception(), UnavailableError)
    finally:
        ref.close()
    assert any(f.exception() is not None for f in futures)


# -- how often ------------------------------------------------------------------
def test_speculation_reads_every_step_before_the_next(gpt):
    # a prompt that repeats, so that the proposer drafts
    prompt = np.tile(np.arange(3, 9, dtype=np.int32), 4)
    plain = engine(gpt, batch=2, name="ahead-plain")
    spec = engine(gpt, batch=2, speculative_k=2, name="ahead-spec")
    try:
        plain.warmup()
        spec.warmup()
        want = serial_reference(plain, prompt, 24)
        out = np.asarray(spec.submit(prompt, 24).result(120)).tolist()
        st = settled(spec, 1)
    finally:
        plain.close()
        spec.close()
    assert out == want
    assert st["decode_steps"] > 0 and st["decode_steps_ahead"] == 0
    assert st["decode_tokens_stale"] == 0


def test_steady_decoding_runs_ahead_in_most_steps(family):
    name, m = family
    prompts = prompts_of((9, 14, 3, 20), seed=9)
    eng = engine(m, name="ahead-steady-" + name)
    try:
        eng.warmup()
        futures = [eng.submit(p, 40) for p in prompts]
        for f in futures:
            f.result(300)
        st = settled(eng, len(prompts))
    finally:
        eng.close()
    # only the step behind an admission has nothing unread before it
    assert st["decode_steps_ahead"] / st["decode_steps"] > 0.8
