import numpy as np
import pytest

from benchmarks.harness.loader import load_json, load_module

GEN = load_module("generators", "requests")
CFG = {"vocab_size": 50304}


def _same(a, b):
    return (len(a) == len(b) and all(
        x["due_s"] == y["due_s"] and x["max_new_tokens"] == y["max_new_tokens"]
        and np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b)))


@pytest.mark.parametrize("mix", ["chat_open", "docs_closed"])
def test_bit_identical_for_one_seed_and_different_across_seeds(mix):
    tr = load_json("traffic", mix + ".json")
    big = 2 ** 31 + 12345
    a, b = GEN.generate(tr, CFG, big, 10), GEN.generate(tr, CFG, big, 10)
    c = GEN.generate(tr, CFG, big + 1, 10)
    assert _same(a, b) and not _same(a, c)


@pytest.mark.parametrize("mix", ["chat_open", "docs_closed"])
def test_every_seed_gets_the_same_schedule_with_other_tokens(mix):
    tr = load_json("traffic", mix + ".json")
    a, c = GEN.generate(tr, CFG, 1, 10), GEN.generate(tr, CFG, 2, 10)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in c]
    assert not any(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, c))
    other = GEN.generate(dict(tr, schedule_seed=tr.get("schedule_seed", 0) + 1),
                         CFG, 1, 10)
    assert sorted(len(r["prompt"]) for r in other) == \
        sorted(len(r["prompt"]) for r in a)           # the same sizes
    assert [len(r["prompt"]) for r in other] != [len(r["prompt"]) for r in a]
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    assert max(len(r["prompt"]) for r in a) <= max(tr["prompt_buckets"])


def test_open_loop_offers_the_files_rate():
    tr = dict(load_json("traffic", "chat_open.json"), rate_per_s=10.0)
    reqs = GEN.generate(tr, CFG, 5, 30)
    due = sorted(r["due_s"] for r in reqs)
    assert len(reqs) == round(tr["rate_per_s"] * (30 + tr["warm_seconds"]))
    assert due[0] >= -tr["warm_seconds"] and due[-1] < 30
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)


def test_fixed_batch_rows_all_differ_and_repeat_per_seed():
    gen = load_module("generators", "fixed_batch")
    tr = load_json("traffic", "pretrain_s128.json")
    cfg = load_json("configs", "bert_base_pretrain.json")
    a, b = gen.generate(tr, cfg, 2 ** 31 + 7, ), gen.generate(tr, cfg, 2 ** 31 + 7)
    c = gen.generate(tr, cfg, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    assert len({row.tobytes() for row in a["input_ids"]}) == tr["batch"]
    assert a["masked_positions"].shape == (256, 20)
    assert np.array_equal(a["mlm_labels"], np.take_along_axis(
        a["input_ids"], a["masked_positions"], axis=1))
