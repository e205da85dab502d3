"""The per-layer readers over the engine's own counters
(``harness/engine_lib.py``): their arithmetic on a hand-made window, that
they find nothing (and do not raise) on a program without the counters,
which cells they go to, and that a rehearsed serving run prints every
counter they read and names its phases as the harness knows them."""
import importlib.util
import json
import os

import pytest

from benchmarks.harness import engine_lib, loader

# one window's counter deltas, made by hand: 100 decode steps of 40 ms, 10
# admissions of 3 rows into a [32 x 128] program, 2 s of waiting
COUNTERS = {
    "loop_us_sched": 50_000, "loop_us_admit_host": 30_000,
    "loop_us_admit_device": 570_000, "loop_us_decode_pack": 100_000,
    "loop_us_decode_device": 4_000_000, "loop_us_harvest": 200_000,
    "loop_us_publish": 50_000, "loop_us_wait": 2_000_000,
    "loop_us_total": 7_000_000, "decode_steps": 100, "live_slot_steps": 2400,
    "admit_steps": 10, "admit_rows": 30, "admit_tokens": 2048,
    "admit_token_slots": 40960,
    "kv_pages_live_steps": 25_600, "kv_page_slots_steps": 204_800,
    "queue_wait_us": 600_000, "ttft_us": 2_400_000,
}
EXPECTED = {
    "decode_step_ms": 40.0,
    "host_ms_per_step": 4.3,
    "decode_live_slots": 24.0,
    "prefill_useful_share": 5.0,
    "kv_live_page_share": 12.5,
    "queue_wait_mean_ms": 20.0,
    "ttft_mean_ms": 80.0,
    "admit_call_ms": 57.0,
}
#: the entries these readers have in the two GPT-2 cells (PR 49 retired
#: ``decode_live_slots.serve``: a closed loop that is always full reads 32)
NEW = [f"{name}.{suffix}" for name in EXPECTED
       for suffix in (("chat",) if name.endswith("_mean_ms")
                      or name == "decode_live_slots" else ("serve", "chat"))]


def _ev(counters):
    # "seconds" is there to be ignored: no reader may divide by it
    return {"facts": {"counters": counters}, "seconds": 1e9, "trace": None}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value_and_none(name):
    read = getattr(engine_lib, name)
    assert read(_ev(COUNTERS)) == pytest.approx(EXPECTED[name])
    # a program from before the counters: nothing to read, nothing raised
    assert read(_ev({"decode_steps": 100, "tokens": 3000})) is None
    assert read({"facts": {}}) is None
    # each counter a reader uses, taken away or (a divisor) at zero
    used = [k for k in COUNTERS
            if read(_ev({**COUNTERS, k: COUNTERS[k] + 1}))
            != read(_ev(COUNTERS))]
    assert used
    for k in used:
        assert read(_ev({c: v for c, v in COUNTERS.items() if c != k})) \
            is None
    zero = {k: 0 for k in ("decode_steps", "admit_token_slots",
                           "kv_page_slots_steps", "admit_rows",
                           "admit_steps")}
    assert read(_ev({**COUNTERS, **zero})) is None


@pytest.mark.parametrize("full", NEW)
def test_an_entry_re_exports_its_reader(full):
    # tests/test_manifest.py holds every entry to its file, cells and fields
    (m,) = [m for m in loader.manifest()["per_layer"] if m["name"] == full]
    assert m["source"] == "program_counter"
    assert m["moves"] == {"serve": "serve_tok_s",
                          "chat": "req_latency_p95_ms"}[full.rsplit(".", 1)[1]]
    assert loader.load_module("layer_metrics", full).read(
        _ev(COUNTERS)) == pytest.approx(EXPECTED[full.rsplit(".", 1)[0]])


def test_serve_readers_go_to_docs_closed_and_chat_readers_to_chat_open():
    man = loader.manifest()
    got = {cell: {m["name"] for m in loader.metrics_of(cell, man)[1]}
           for cell in ("gpt2_small.docs_closed", "gpt2_small.chat_open",
                        "bert_base.pretrain_s128")}
    assert got["gpt2_small.docs_closed"] & set(NEW) == {
        n for n in NEW if n.endswith(".serve")}
    assert got["gpt2_small.chat_open"] & set(NEW) == {
        n for n in NEW if n.endswith(".chat")}
    assert not got["bert_base.pretrain_s128"] & set(NEW)


def test_a_rehearsed_chat_run_prints_every_loop_counter(capsys):
    from benchmarks.harness.context import SERVE_PHASES
    from paddle_tpu.serving.metrics import LOOP_COUNTERS, LOOP_PHASES

    # the names an idle gap of the device is attributed to are the loop's
    assert SERVE_PHASES == tuple("serve/" + p for p in LOOP_PHASES)

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(loader.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    capsys.readouterr()
    rc = mod.main(["--workload", "gpt2_small.chat_open", "--seed",
                   str(2 ** 31 + 17), "--seconds", "2", "--trace", "0",
                   "--rehearse"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    delta = next(l["counter_deltas"] for l in lines if "counter_deltas" in l)
    assert set(LOOP_COUNTERS) <= set(delta)
    phases = sum(v for k, v in delta.items()
                 if k.startswith("loop_us_") and k != "loop_us_total")
    assert abs(phases - delta["loop_us_total"]) \
        <= 0.01 * delta["loop_us_total"]
    # what the window's counters give the readers, as a traced run would
    ev = _ev(delta)
    for name in EXPECTED:
        assert getattr(engine_lib, name)(ev) is not None, name
    assert 0 < engine_lib.decode_live_slots(ev) <= 4  # serve_rehearse slots
    for name in ("prefill_useful_share", "kv_live_page_share"):
        assert 0 < getattr(engine_lib, name)(ev) <= 100, name
