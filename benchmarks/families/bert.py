"""BERT family: the parameter layout, the seeded weights, the program under
test built the way bench.py builds it (``fluid.Program`` -> ``Executor``),
and the model's FLOP count.  A configuration names this file by
``"family": "bert"``."""
import numpy as np

from benchmarks.harness import flops, weights

REFERENCE = "bert"


def param_spec(cfg):
    """name -> (shape, kind), in ``named_parameters()`` order."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    spec = {
        "bert.embeddings.word.weight": ((cfg["vocab_size"], d), "matrix"),
        "bert.embeddings.position.weight":
            ((cfg["max_position_embeddings"], d), "matrix"),
        "bert.embeddings.token_type.weight":
            ((cfg["type_vocab_size"], d), "matrix"),
        "bert.embeddings.ln.weight": ((d,), "gain"),
        "bert.embeddings.ln.bias": ((d,), "bias"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.layers.{i}."
        spec.update({
            p + "attn.qkv.weight": ((d, 3 * d), "matrix"),
            p + "attn.qkv.bias": ((3 * d,), "bias"),
            p + "attn.out.weight": ((d, d), "matrix"),
            p + "attn.out.bias": ((d,), "bias"),
            p + "ln1.weight": ((d,), "gain"),
            p + "ln1.bias": ((d,), "bias"),
            p + "mlp.fc1.weight": ((d, ff), "matrix"),
            p + "mlp.fc1.bias": ((ff,), "bias"),
            p + "mlp.fc2.weight": ((ff, d), "matrix"),
            p + "mlp.fc2.bias": ((d,), "bias"),
            p + "ln2.weight": ((d,), "gain"),
            p + "ln2.bias": ((d,), "bias"),
        })
    spec.update({
        "bert.pooler.weight": ((d, d), "matrix"),
        "bert.pooler.bias": ((d,), "bias"),
        "transform.weight": ((d, d), "matrix"),
        "transform.bias": ((d,), "bias"),
        "ln.weight": ((d,), "gain"),
        "ln.bias": ((d,), "bias"),
        "nsp.weight": ((d, 2), "matrix"),
        "nsp.bias": ((2,), "bias"),
    })
    return spec


def make_weights(cfg, seed):
    return weights.make_weights(param_spec(cfg), seed, cfg["param_dtype"])


def train_flops_per_sample(cfg, traffic):
    return flops.bert_pretrain_flops_per_sequence(
        cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["intermediate_size"], cfg["vocab_size"], traffic["seq_len"],
        traffic["max_predictions"])


def build_program(cfg, traffic, weight_dict, feeds):
    """The program bench.py's BERT config builds, holding ``weight_dict``.
    Returns ``(exe, main, loss, names)`` with ``names`` mapping each
    program-scope name to the parameter's own name."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu import optimizer as popt
    from paddle_tpu.models import BertForPretraining
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.static.builders import layer_op
    from paddle_tpu.static.graph import record_call

    pcfg = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"], dropout=cfg["dropout"],
        layer_norm_epsilon=cfg["layer_norm_eps"])
    paddle.seed(0)
    net = BertForPretraining(pcfg).astype(cfg["param_dtype"])
    own = dict(net.named_parameters())
    if set(own) != set(weight_dict):
        raise RuntimeError("BERT parameter names differ from the family's "
                           f"spec: {set(own) ^ set(weight_dict)}")
    for name, p in own.items():
        w = weight_dict[name]
        if tuple(p.shape) != tuple(w.shape):
            raise RuntimeError(f"{name}: {p.shape} vs {w.shape}")
        p.set_value(w)
    batch, seq = feeds["input_ids"].shape
    max_pred = feeds["masked_positions"].shape[1]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids_v = fluid.data("input_ids", [batch, seq], "int32")
        tt_v = fluid.data("token_type_ids", [batch, seq], "int32")
        am_v = fluid.data("attention_mask", [batch, seq], "int32")
        mp_v = fluid.data("masked_positions", [batch, max_pred], "int32")
        mlm_y = fluid.data("mlm_labels", [batch, max_pred], "int32")
        nsp_y = fluid.data("nsp_labels", [batch, 1], "int32")
        mlm_logits, nsp_logits = layer_op(
            net, ids_v, prefix="bert", extra_args=(tt_v, am_v, mp_v))
        loss = record_call(net.loss, mlm_logits, nsp_logits, mlm_y, nsp_y,
                           prefix="bert_loss")
        opt = cfg["optimizer"]
        rate = opt["learning_rate"]
        if opt.get("warmup_steps"):
            rate = popt.lr.LinearWarmup(
                learning_rate=rate, warmup_steps=opt["warmup_steps"],
                start_lr=0.0, end_lr=rate)
        popt.AdamW(learning_rate=rate,
                   weight_decay=opt["weight_decay"],
                   multi_precision=True).minimize(loss)
    names = dict(zip(main.scope, own))
    for scope_name, name in names.items():
        if tuple(main.scope[scope_name].shape) != tuple(
                weight_dict[name].shape):
            raise RuntimeError(f"scope order differs at {scope_name}")
    exe = fluid.Executor()
    exe.run(startup)
    return exe, main, loss, names


def leaf_parts(name):
    """The fused QKV projection is three leaves when norms are compared."""
    return 3 if ".attn.qkv." in name else 1


TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 128, "vocab_size": 512,
        "max_position_embeddings": 64}
