import pytest

from benchmarks.harness import flops
from benchmarks.harness.loader import load_json, load_module


def test_bert_base_by_hand():
    # encoder weights per layer: 4*768^2 + 2*768*3072 = 7,077,888 -> 12
    # layers = 84,934,656 multiply-adds per token; attention 2*2*128*768 per
    # token per layer
    per_token = 2 * 84_934_656 + 12 * 4 * 128 * 768
    enc = 128 * per_token
    head = 20 * (2 * 768 * 768 + 2 * 768 * 30522)
    extra = 2 * 768 * 768 + 2 * 768 * 2
    want = 3 * (enc + head + extra)
    got = flops.bert_pretrain_flops_per_sequence(768, 12, 3072, 30522, 128, 20)
    assert got == want
    assert got / 1e9 == pytest.approx(70, abs=0.5)   # not bench.py's 84.5


def test_family_counts_from_the_cells_own_files():
    cfg = load_json("configs", "bert_base_pretrain.json")
    traffic = load_json("traffic", "pretrain_s128.json")
    fam = load_module("families", "bert")
    assert fam.train_flops_per_sample(cfg, traffic) == \
        flops.bert_pretrain_flops_per_sequence(768, 12, 3072, 30522, 128, 20)

