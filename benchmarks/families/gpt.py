"""GPT family: the parameter layout, the seeded weights, the model under
test (``GPTForCausalLM``) holding them, and the model's FLOP count.  A
configuration names this file by ``"family": "gpt"``."""
from benchmarks.harness import weights

REFERENCE = "gpt2"


def param_spec(cfg):
    d = cfg["n_embd"]
    ff = cfg.get("n_inner") or 4 * d
    spec = {
        "gpt.wte.weight": ((cfg["vocab_size"], d), "matrix"),
        "gpt.wpe.weight": ((cfg["n_positions"], d), "matrix"),
    }
    for i in range(cfg["n_layer"]):
        p = f"gpt.blocks.{i}."
        spec.update({
            p + "ln1.weight": ((d,), "gain"),
            p + "ln1.bias": ((d,), "bias"),
            p + "attn.qkv.weight": ((d, 3 * d), "matrix"),
            p + "attn.qkv.bias": ((3 * d,), "bias"),
            p + "attn.out.weight": ((d, d), "matrix"),
            p + "attn.out.bias": ((d,), "bias"),
            p + "ln2.weight": ((d,), "gain"),
            p + "ln2.bias": ((d,), "bias"),
            p + "mlp.fc1.weight": ((d, ff), "matrix"),
            p + "mlp.fc1.bias": ((ff,), "bias"),
            p + "mlp.fc2.weight": ((ff, d), "matrix"),
            p + "mlp.fc2.bias": ((d,), "bias"),
        })
    spec.update({"gpt.ln_f.weight": ((d,), "gain"),
                 "gpt.ln_f.bias": ((d,), "bias")})
    return spec


def make_weights(cfg, seed):
    return weights.make_weights(param_spec(cfg), seed, cfg["param_dtype"])


def build_model(cfg, weight_dict, dropout=0.0):
    """``GPTForCausalLM`` at the configuration's sizes holding
    ``weight_dict``."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    d = cfg["n_embd"]
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=d,
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg.get("n_inner") or 4 * d,
        max_position=cfg["n_positions"], dropout=dropout,
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        dtype=cfg["param_dtype"]))
    own = dict(model.named_parameters())
    if set(own) != set(weight_dict):
        raise RuntimeError("GPT parameter names differ from the family's "
                           f"spec: {set(own) ^ set(weight_dict)}")
    for name, p in own.items():
        w = weight_dict[name]
        if tuple(p.shape) != tuple(w.shape) or str(p.dtype) != str(w.dtype):
            raise RuntimeError(f"{name}: {p.shape} {p.dtype} vs "
                               f"{w.shape} {w.dtype}")
        p.set_value(w)
    return model


def leaf_parts(name):
    """The fused QKV projection is three leaves when norms are compared."""
    return 3 if ".attn.qkv." in name else 1


TINY = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 128,
        "vocab_size": 512}
