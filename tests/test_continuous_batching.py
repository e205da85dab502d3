"""Slot-level continuous batching (serving/generation.py).

Covers the scheduler's contract, every token held to uncached greedy
(the model's plain ``forward``): identity under staggered mid-decode
admission; slot eviction/re-admission without KV contamination; the
closed compile set (``len(prompt_buckets) + 3`` for the default engine,
which speculates; zero post-warmup recompiles); EOS; transient-failure
restart with a rebuilt pool; and analysis rule S603 (sustained slot
starvation while the queue is non-empty).  The staggered-admission and
restart cases run on the default-constructed engine and on one with
small pages.
"""
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnavailableError
from paddle_tpu.serving import GenerationEngine

#: one more executable wherever several devices make up the global mesh
#: (the suite's eight): a step's outputs carry the mesh's sharding, so the
#: host-built fresh pool of warm-up is another abstract input and the
#: step traces once more for it (GenerationEngine.warmup's docstring)
FRESH_TRACE = int(len(jax.devices()) > 1)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    pt.seed(4321)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_position=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref_greedy(model, prompt, n, eos=None):
    import jax.numpy as jnp
    ids, outs = list(map(int, prompt)), []
    for _ in range(n):
        logits = np.asarray(model(jnp.asarray([ids], jnp.int32)))[0]
        nxt = int(np.argmax(logits[-1]))
        outs.append(nxt)
        ids.append(nxt)
        if eos is not None and nxt == eos:
            break
    return outs


@pytest.mark.parametrize("engine_kw", [
    {}, {"kv_page_size": 8, "speculative_k": 3}], ids=["default", "page8_k3"])
def test_token_identity_staggered_admission(model, engine_kw):
    # one long request pins a slot while shorts are admitted mid-decode
    # into the other slot as it recycles, pages allocating and freeing
    # underneath — every output must match uncached greedy
    prompts = [(np.arange(10) * 5 + 2) % 97, np.arange(3) % 97,
               (np.arange(6) * 3) % 97, (np.arange(4) * 7 + 1) % 97,
               (np.arange(5) * 11 + 3) % 97]
    budgets = [14, 3, 4, 5, 3]
    refs = [_ref_greedy(model, p, b) for p, b in zip(prompts, budgets)]
    with GenerationEngine(model, prompt_buckets=[8, 16], batch_size=2,
                          name="cb-stagger", **engine_kw) as eng:
        # 2 admits + unified step + its [B, 1] fast trace + CoW op
        # + the fresh-pool trace of the step (on the suite's 8-device
        # mesh a step's output pool carries the mesh's sharding, so
        # _init_pool's host-built pool is another abstract input and
        # the step traces once more for it: FRESH_TRACE);
        # eviction is a host table edit with no executable
        assert eng.warmup() == 5 + FRESH_TRACE
        futs = [eng.submit(prompts[0], budgets[0]),
                eng.submit(prompts[1], budgets[1])]
        for p, b in zip(prompts[2:], budgets[2:]):
            time.sleep(0.02)  # long request is mid-decode by now
            futs.append(eng.submit(p, b))
        gens = [f.result(120) for f in futs]
        for g, ref in zip(gens, refs):
            assert g.tolist() == ref
        # slot and page churn never reopened the compile set
        assert eng.compile_count == 5 + FRESH_TRACE
        st = eng.stats()
        assert st["kv_pages_free"] == eng._pool.num_pages  # all returned
        assert st["kv_pages_leaked"] == 0


def test_slot_reuse_has_no_kv_contamination(model):
    # batch_size=1: every request reuses THE one slot; admission must
    # fully replace the previous occupant's pages
    prompts = [(np.arange(7) * 13 + 5) % 97, np.arange(2) % 97,
               (np.arange(8) * 3 + 1) % 97]
    with GenerationEngine(model, prompt_buckets=[8], batch_size=1,
                          name="cb-reuse") as eng:
        # 1 admit + step + fast step + cow + the fresh-pool trace
        assert eng.warmup() == 4 + FRESH_TRACE
        for p in prompts:
            assert (eng.generate(p, 5, timeout=120).tolist()
                    == _ref_greedy(model, p, 5))
        assert eng.compile_count == 4 + FRESH_TRACE
        st = eng.stats()
        assert st["admitted"] == 3
        assert st["decode_steps"] > 0
        assert "slot_occupancy" in st
        assert "queue_age_ms" in st


@pytest.mark.parametrize("slots", [2, 1], ids=["two_slots", "one_slot"])
def test_eos_stops_early(model, slots):
    probe = _ref_greedy(model, np.arange(4) % 97, 8)
    eos = probe[1]  # stop at this token's FIRST occurrence
    expect = probe[: probe.index(eos) + 1]
    assert len(expect) < 8
    with GenerationEngine(model, prompt_buckets=[8], batch_size=slots,
                          eos_token_id=eos, name="cb-eos") as eng:
        gen = eng.generate(np.arange(4) % 97, max_new_tokens=8, timeout=120)
        assert gen.tolist() == expect
        assert gen[-1] == eos


@pytest.mark.parametrize("engine_kw", [
    {}, {"kv_page_size": 8, "speculative_k": 2}], ids=["default", "page8_k2"])
def test_transient_failure_restarts_and_tokens_survive(model, engine_kw):
    from paddle_tpu.resilience.faults import FaultPlan
    with GenerationEngine(model, prompt_buckets=[8], batch_size=2,
                          circuit_breaker=False, name="cb-restart",
                          **engine_kw) as eng:
        eng.warmup()
        p = (np.arange(5) * 9 + 4) % 97
        ref = _ref_greedy(model, p, 6)
        assert eng.generate(p, 6, timeout=120).tolist() == ref
        plan = FaultPlan.parse(
            "site=serving.decode,nth=1,error=TransientDeviceError")
        with plan:
            # admission trips the fault; greedy decode is deterministic,
            # so the restarted request regenerates the exact same tokens
            assert eng.generate(p, 6, timeout=120).tolist() == ref
        assert plan.stats()["serving.decode"]["fired"] == 1
        st = eng.stats()
        assert st["restarts"] >= 1
        # the rebuilt pool starts clean
        assert st["kv_pages_leaked"] == 0


def test_s603_fires_on_starved_queue(model):
    from paddle_tpu.analysis import RetraceMonitor

    class _AlwaysOpen:  # deterministic stand-in for an open circuit
        def allow(self, key):
            return False

        def record_success(self, key):
            pass

        def record_failure(self, key):
            pass

    with RetraceMonitor(budget=8) as mon:
        eng = GenerationEngine(model, prompt_buckets=[8], batch_size=1,
                               name="cb-starve")
        try:
            eng.warmup()
            eng.breaker = _AlwaysOpen()
            fut = eng.submit(np.arange(3) % 97, 4)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if eng.stats()["starved_steps_after_warm"] > 8:
                    break
                time.sleep(0.02)
            assert eng.stats()["starved_steps_after_warm"] > 8
            time.sleep(0.25)  # let a publish tick carry the gauges
            assert eng.stats()["queue_depth"] >= 1
            diags = [d for d in mon.diagnostics() if d.rule == "S603"]
            assert diags, mon.diagnostics()
        finally:
            eng.close(drain=False, timeout=10)
        assert isinstance(fut.exception(timeout=5), UnavailableError)
