"""The controls of "How correct is decided", kept at a size a test run can
hold.  A control is the plain reference put in the program's place and
computed one precision step below what the configuration states (fp8
matmul operands for the bfloat16 BERT cell, bfloat16 throughout for the
float32 GPT-2 cells).  On the chip, at the cells' own sizes, the controls
were read through ``run.py --control`` (PERF.md has the readings and the
limits set from them); here the same functions are held to limits of this
test's own size: the control comes out as not correct, a sound stand-in as
correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import checks as hc
from benchmarks.harness import loader

#: test-size limits, set the way the cells' are: above what sound runs read
#: on three seeds here (loss gap <= 1.5e-5; gap 0) and below the control's
#: smallest (loss gap >= 8.9e-5; mean gap >= 4.7e-7)
BERT_LOSS_GAP_LIMIT = 4.5e-5
GPT_MEAN_GAP_LIMIT = 1e-7


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_bert_fp8_control_fails_the_loss_limit(seed):
    fam = loader.load_module("families", "bert")
    ref = loader.load_module("reference", "bert")
    gen = loader.load_module("generators", "fixed_batch")
    cfg = dict(loader.load_json("configs", "bert_base_pretrain.json"),
               hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
               intermediate_size=512, vocab_size=2048,
               max_position_embeddings=64)
    tr = {"task": "mlm_nsp", "batch": 32, "seq_len": 32, "max_predictions": 6}
    w = fam.make_weights(cfg, seed)
    p0 = {k: v.astype(jnp.float32) for k, v in w.items()}
    feeds = {k: jnp.asarray(v) for k, v in gen.generate(tr, cfg, seed).items()}

    def follow(mode):
        losses, p, st = ref.train_steps(p0, feeds, cfg, 3, 1e-4, 0.01,
                                        mode=mode, served_dtype="bfloat16",
                                        block_rows=16)
        m = hc.split_parts(lambda x: float(jnp.linalg.norm(x)), st["m"],
                           fam.leaf_parts)
        return losses, m

    truth, m_truth = follow("f32")
    rows = []
    got = {}
    for mode in ("bf16", "fp8"):  # the configuration's precision, then below
        losses, m = follow(mode)
        ck = hc.Checks(rows.append)
        ck.upper("loss_gap", max(abs(a - b) / abs(b)
                                 for a, b in zip(losses, truth)),
                 BERT_LOSS_GAP_LIMIT)
        got[mode] = ck.correct
        assert np.isfinite(hc.worst_leaf_gap(m, m_truth)[0])
    assert got == {"bf16": True, "fp8": False}, rows


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_gpt2_bf16_control_fails_the_gap_limit(seed):
    fam = loader.load_module("families", "gpt")
    ref = loader.load_module("reference", "gpt2")
    cfg = dict(loader.load_json("configs", "gpt2_small_serve.json"),
               n_embd=128, n_layer=4, n_head=4, n_positions=128,
               vocab_size=4096)
    w = fam.make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 4096, size=int(n)).astype(np.int32)
               for n in rng.integers(8, 48, size=16)]
    rest, stacks = ref.stack_layers(w, cfg["n_layer"])

    @jax.jit
    def greedy(ids, pos):
        with jax.default_matmul_precision("highest"):
            return jnp.argmax(ref.logits_at(rest, stacks, ids, pos, cfg,
                                            "f32"), -1)

    served = []  # a sound server: greedy tokens of the stated precision
    for p in prompts:
        hist, out = list(p), []
        for _ in range(24):
            ids = np.zeros((1, 128), np.int32)
            ids[0, :len(hist)] = hist
            t = int(greedy(jnp.asarray(ids), jnp.asarray(
                [[len(hist) - 1]], jnp.int32))[0, 0])
            out.append(t)
            hist.append(t)
        served.append(np.array(out, np.int32))
    res = ref.served_token_gaps(w, cfg, prompts, served, control_mode="bf16")
    sound = np.concatenate([r["gap"] for r in res])
    control = np.concatenate([r["control_gap"] for r in res])
    rows = []
    ok = hc.Checks(rows.append)
    ok.upper("mean_gap", float(sound.mean()), GPT_MEAN_GAP_LIMIT)
    bad = hc.Checks(rows.append)
    bad.upper("mean_gap", float(control.mean()), GPT_MEAN_GAP_LIMIT)
    assert ok.correct and not bad.correct, rows
    assert (control >= 0).all() and len(control) == 16 * 24


def test_a_nan_never_passes():
    rows = []
    ck = hc.Checks(rows.append)
    ck.upper("x", float("nan"), 1.0)
    assert not ck.correct
    assert not hc.Checks(rows.append).correct      # nothing compared
    gap, _ = hc.worst_leaf_gap({"a": float("nan"), "b": 1.0},
                               {"a": 1.0, "b": 1.0})
    assert gap != gap
