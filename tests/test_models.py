"""Flagship model tests (GPT decoder, BERT encoder): shapes, causality,
masking, loss semantics, tiny-scale convergence, TP-sharded training parity
(mirrors the reference's dist_transformer.py model-level tests)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as popt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.mesh import build_mesh, set_mesh
from paddle_tpu.models import (
    BertForPretraining,
    BertForSequenceClassification,
    GPTForCausalLM,
    bert_tiny,
    gpt_tiny,
)


@pytest.fixture(autouse=True)
def reset_mesh():
    set_mesh(build_mesh())
    yield
    set_mesh(build_mesh())
    fleet._initialized = False


class TestGPT:
    def test_forward_shapes(self):
        paddle.seed(0)
        net = GPTForCausalLM(gpt_tiny())
        ids = jnp.asarray(np.random.randint(0, 128, (2, 10)), jnp.int32)
        logits = net(ids)
        assert logits.shape == (2, 10, 128)

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        paddle.seed(0)
        net = GPTForCausalLM(gpt_tiny())
        net.eval()
        rng = np.random.RandomState(0)
        ids_a = rng.randint(0, 128, (1, 12)).astype(np.int32)
        ids_b = ids_a.copy()
        ids_b[0, -1] = (ids_b[0, -1] + 1) % 128
        la = np.asarray(net(jnp.asarray(ids_a)))
        lb = np.asarray(net(jnp.asarray(ids_b)))
        np.testing.assert_allclose(la[0, :-1], lb[0, :-1], atol=1e-5)
        assert not np.allclose(la[0, -1], lb[0, -1])

    def test_loss_decreases(self):
        paddle.seed(0)
        cfg = gpt_tiny(num_layers=1, hidden_size=16, num_heads=2)
        net = GPTForCausalLM(cfg)
        # repetitive sequence is learnable
        ids = np.tile(np.arange(8, dtype=np.int32), (4, 2))
        model = paddle.Model(net)
        model.prepare(optimizer=popt.Adam(learning_rate=1e-2), loss=net.loss)
        l0, _ = model.train_batch([ids], [ids])
        for _ in range(60):
            l1, _ = model.train_batch([ids], [ids])
        assert l1 < l0 * 0.5, (l0, l1)

    def test_tied_lm_head(self):
        net = GPTForCausalLM(gpt_tiny())
        names = [n for n, _ in net.named_parameters()]
        assert not any("lm_head" in n for n in names)  # tied to wte


class TestBert:
    def test_forward_shapes(self):
        paddle.seed(0)
        net = BertForPretraining(bert_tiny())
        ids = jnp.asarray(np.random.randint(0, 128, (2, 12)), jnp.int32)
        mlm, nsp = net(ids)
        assert mlm.shape == (2, 12, 128)
        assert nsp.shape == (2, 2)

    def test_attention_mask_blocks_pad(self):
        """Masked (pad) positions must not influence unmasked outputs."""
        paddle.seed(0)
        net = BertForSequenceClassification(bert_tiny(), num_classes=3)
        net.eval()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (1, 10)).astype(np.int32)
        mask = np.ones((1, 10), np.float32)
        mask[0, 7:] = 0.0
        out_a = np.asarray(net(jnp.asarray(ids), attention_mask=jnp.asarray(mask)))
        ids2 = ids.copy()
        ids2[0, 8] = (ids2[0, 8] + 3) % 128  # change a padded token
        out_b = np.asarray(net(jnp.asarray(ids2), attention_mask=jnp.asarray(mask)))
        np.testing.assert_allclose(out_a, out_b, atol=1e-5)

    def test_mlm_loss_ignores_unmasked(self):
        paddle.seed(0)
        net = BertForPretraining(bert_tiny())
        ids = jnp.asarray(np.random.randint(0, 128, (2, 8)), jnp.int32)
        mlm, nsp = net(ids)
        labels_none = np.full((2, 8), -100, np.int64)
        labels_none[0, 2] = 5
        nsp_labels = np.zeros((2, 1), np.int64)
        l1 = float(net.loss(mlm, nsp, jnp.asarray(labels_none), jnp.asarray(nsp_labels)))
        assert np.isfinite(l1)
        # all-ignored MLM → only NSP contributes
        all_ignored = np.full((2, 8), -100, np.int64)
        l2 = float(net.loss(mlm, nsp, jnp.asarray(all_ignored), jnp.asarray(nsp_labels)))
        assert l2 < l1 + 10  # finite, no nan from 0/0

    def test_classification_trains(self):
        paddle.seed(0)
        net = BertForSequenceClassification(bert_tiny(num_layers=1), num_classes=2)
        rng = np.random.RandomState(0)
        # class = token[0] parity
        ids = rng.randint(0, 128, (32, 8)).astype(np.int32)
        y = (ids[:, 0] % 2).astype(np.int64).reshape(-1, 1)
        model = paddle.Model(net)
        model.prepare(optimizer=popt.Adam(learning_rate=1e-3),
                      loss=nn.CrossEntropyLoss())
        l0, _ = model.train_batch([ids], [y])
        for _ in range(80):
            l1, _ = model.train_batch([ids], [y])
        assert l1 < l0, (l0, l1)

    def test_question_answering_finetunes(self):
        """SQuAD fine-tune shape: the QA head learns to
        point start/end at a marker token's span."""
        from paddle_tpu.models import BertForQuestionAnswering

        paddle.seed(0)
        net = BertForQuestionAnswering(bert_tiny(num_layers=1))
        rng = np.random.RandomState(0)
        B, S, MARK = 32, 12, 7
        ids = rng.randint(8, 128, (B, S)).astype(np.int32)
        starts = rng.randint(0, S - 1, (B,))
        for i, s in enumerate(starts):
            ids[i, s] = MARK
            ids[i, s + 1] = MARK
        start_pos = starts.astype(np.int64)[:, None]
        end_pos = (starts + 1).astype(np.int64)[:, None]

        model = paddle.Model(net, inputs=["ids"], labels=["s", "e"])
        model.prepare(optimizer=popt.Adam(learning_rate=2e-3),
                      loss=net.loss)
        l0, _ = model.train_batch([ids], [start_pos, end_pos])
        for _ in range(120):
            l1, _ = model.train_batch([ids], [start_pos, end_pos])
        assert l1 < l0 * 0.3, (l0, l1)
        start_logits, end_logits = net(jnp.asarray(ids))
        acc_s = (np.asarray(start_logits).argmax(-1) == starts).mean()
        acc_e = (np.asarray(end_logits).argmax(-1) == starts + 1).mean()
        assert acc_s > 0.8 and acc_e > 0.8, (acc_s, acc_e)

    def test_qa_loss_ignores_truncated_answers(self):
        """Positions beyond the sequence (truncated answers) must be
        skipped, not clamped toward the last token."""
        from paddle_tpu.models import BertForQuestionAnswering

        rng = np.random.RandomState(0)
        s_log = jnp.asarray(rng.randn(4, 8).astype(np.float32))
        e_log = jnp.asarray(rng.randn(4, 8).astype(np.float32))
        in_range = np.array([1, 2, 3, 4], np.int64)[:, None]
        base = BertForQuestionAnswering.loss(s_log, e_log, in_range,
                                             in_range)
        # one example's answer truncated away → OOB position
        oob = in_range.copy()
        oob[0, 0] = 400
        mixed = BertForQuestionAnswering.loss(s_log, e_log, oob, in_range)
        assert np.isfinite(float(mixed))
        assert float(mixed) != float(base)
        # exact decomposition: the OOB start example is dropped from the
        # start-CE mean; the end-CE still averages all four
        import paddle_tpu.nn.functional as F

        want = 0.5 * (float(F.cross_entropy(s_log[1:], in_range[1:]))
                      + float(F.cross_entropy(e_log, in_range)))
        np.testing.assert_allclose(float(mixed), want, rtol=1e-6)


class TestGPTFlashRouting:
    def test_use_flash_gate(self):
        import jax

        from paddle_tpu.models.gpt import GPTConfig, ParallelAttention

        attn = ParallelAttention(GPTConfig(hidden_size=64, num_heads=1,
                                           dropout=0.1))
        on_tpu = jax.default_backend() == "tpu"
        attn.eval()  # dropout inactive → gate may open
        assert attn._use_flash(4096, None) == on_tpu
        attn.train()  # probs-dropout active → flash must stay off
        assert attn._use_flash(4096, None) is False
        attn.eval()
        assert attn._use_flash(2048, None) is False       # below gate
        assert attn._use_flash(4096, object()) is False   # extra mask
        assert attn._use_flash(4104, None) is False       # ragged blocks

        attn0 = ParallelAttention(GPTConfig(hidden_size=64, num_heads=1,
                                            dropout=0.0))
        attn0.train()  # no dropout configured → train mode is fine
        assert attn0._use_flash(4096, None) == on_tpu

    def test_flash_branch_matches_dense_in_model(self, monkeypatch):
        """Force the gate open and run ParallelAttention.forward through
        the kernel branch (Pallas interpret mode off-TPU) — it must agree
        with the dense einsum branch."""
        from paddle_tpu.models.gpt import GPTConfig, ParallelAttention

        paddle.seed(0)
        attn = ParallelAttention(GPTConfig(hidden_size=128, num_heads=2,
                                           dropout=0.0))
        attn.eval()
        x = jnp.asarray(np.random.RandomState(0).randn(1, 256, 128),
                        jnp.float32)
        dense = np.asarray(attn(x))
        monkeypatch.setattr(ParallelAttention, "_use_flash",
                            lambda self, S, m: m is None)
        flash = np.asarray(attn(x))
        np.testing.assert_allclose(flash, dense, rtol=2e-4, atol=2e-5)


class TestTPParity:
    def test_gpt_tp_matches_single(self):
        """TP=2 forward must equal the single-device forward with the same
        weights (megatron sharding is mathematically transparent)."""
        paddle.seed(0)
        net = GPTForCausalLM(gpt_tiny())
        net.eval()
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 8)), jnp.int32)
        ref = np.asarray(net(ids))

        strat = fleet.DistributedStrategy(
            tensor_parallel=True,
            tensor_parallel_configs={"tensor_parallel_degree": 2})
        fleet.init(is_collective=True, strategy=strat)
        fleet.distributed_model(net)
        assert not net.gpt.blocks[0].attn.qkv.weight.value.sharding.is_fully_replicated

        @jax.jit
        def fwd(ids):
            return net(ids)

        out = np.asarray(fwd(ids))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


class TestGraftEntry:
    def test_dryrun_multichip_8(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)

    def test_entry_compiles_tiny_proxy(self):
        """entry() builds BERT-base (heavy); validate the same path at tiny
        scale + check entry()'s structure lazily."""
        import __graft_entry__ as g

        fn_args = None  # full entry() exercised by the driver on TPU
        net = BertForSequenceClassification(bert_tiny(), num_classes=2)
        net.eval()
        params = net.param_pytree()

        def fn(params, ids):
            return nn.functional_call(net, params, ids, training=False)

        ids = jnp.asarray(np.random.randint(0, 128, (2, 16)), jnp.int32)
        out = jax.jit(fn)(params, ids)
        assert out.shape == (2, 2)
