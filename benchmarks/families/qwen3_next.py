"""Qwen3-Next family: the parameter layout, the seeded weights (made on the
device leaf by leaf in the type they are served in), the model under test
(``HybridForCausalLM`` with this family's options) holding them, and the
tiny preset of the CPU rehearsal.  A configuration names this file by
``"family": "qwen3_next"``.

One chip's share of a layer: the configuration's ``num_experts`` counts the
experts HELD here (``expert_offset`` is the first of them) and
``published.num_experts`` the router's outputs."""
import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import weights
# at import, not in the builders: a program without these options (a parent
# commit under this benchmark) fails here at once, before any weight is made
from paddle_tpu.models.hybrid import (HybridConfig, HybridForCausalLM,
                                      rope_rotate_half)  # noqa: F401
from paddle_tpu.nn import abstract_parameters

REFERENCE = "qwen3_next"


def layer_types(cfg):
    """Layer ``i`` is full attention where ``(i + 1) %
    full_attention_interval == 0``, linear attention elsewhere."""
    n = cfg["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % n == 0 else "linear_attention"
                 for i in range(cfg["num_hidden_layers"]))


def held(cfg):
    """``(first, count)`` of the experts this chip holds."""
    return int(cfg.get("expert_offset", 0)), int(cfg["num_experts"])


def router_width(cfg):
    return int(cfg["published"]["num_experts"])


def param_spec(cfg):
    """``name -> (shape, kind, dtype)``; dtype None is the configuration's
    ``param_dtype``.  The program's layout: ``mixer.qkv`` of a linear layer
    is ``[W_q | W_k | W_v]`` (what the convolution runs over), ``mixer.gate``
    the ``z`` projection and ``mixer.ab`` ``[W_a | W_b]``: together the
    published ``in_proj_qkvz`` and ``in_proj_ba``, columns regrouped.
    ``mixer.qkv`` of a full layer is ``[W_q (per head [q | gate]) | W_k |
    W_v]``.  Gains of kind ``gain0`` are stored as their distance from one."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lk, lv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = 2 * lk * dk + lv * dv
    e, f = held(cfg)[1], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    spec = {"model.embed": ((cfg["vocab_size"], d), "matrix", None)}
    for i, kind in enumerate(layer_types(cfg)):
        p = f"model.blocks.{i}."
        if kind == "full_attention":
            spec.update({
                p + "mixer.qkv": ((d, 2 * h * hd + 2 * hkv * hd), "matrix",
                                  None),
                p + "mixer.q_norm.weight": ((hd,), "gain0", None),
                p + "mixer.k_norm.weight": ((hd,), "gain0", None),
                p + "mixer.out": ((h * hd, d), "matrix", None)})
        else:
            spec.update({
                p + "mixer.qkv": ((d, width), "matrix", None),
                p + "mixer.gate": ((d, lv * dv), "matrix", None),
                p + "mixer.ab": ((d, 2 * lv), "matrix", None),
                p + "mixer.A_log": ((lv,), "a_log", "float32"),
                p + "mixer.dt_bias": ((lv,), "dt_bias", "float32"),
                p + "mixer.conv": ((cfg["linear_conv_kernel_dim"], width),
                                   "matrix", None),
                p + "mixer.o_norm.weight": ((dv,), "gain", None),
                p + "mixer.out": ((lv * dv, d), "matrix", None)})
        spec.update({
            p + "norm1.weight": ((d,), "gain0", None),
            p + "mlp.router": ((d, router_width(cfg)), "matrix", None),
            p + "mlp.expert_gate": ((e, d, f), "matrix", None),
            p + "mlp.expert_up": ((e, d, f), "matrix", None),
            p + "mlp.expert_down": ((e, f, d), "matrix", None),
            p + "mlp.shared_gate": ((d, fs), "matrix", None),
            p + "mlp.shared_up": ((d, fs), "matrix", None),
            p + "mlp.shared_down": ((fs, d), "matrix", None),
            p + "mlp.shared_gating": ((d, 1), "matrix", None),
            p + "norm2.weight": ((d,), "gain0", None)})
    spec.update({"model.norm_f.weight": ((d,), "gain0", None),
                 "head": ((d, cfg["vocab_size"]), "matrix", None)})
    return spec


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _leaf(key, *, shape, kind, dtype):
    """Matrices (the conv taps among them) and the zero-centred gains N(0,
    0.02), the plain gain 1 + N(0, 0.02); ``A_log = log U(1, 16)`` and
    ``dt_bias = softplus^-1(U(0.001, 0.1))``, Mamba-2's initialisation of
    the decay."""
    if kind == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jax.random.uniform(key, shape, jnp.float32, 0.001, 0.1)
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
        w = (1.0 + w) if kind == "gain" else w
    return w.astype(dtype)


def make_weights(cfg, seed):
    key = weights.seed_key(seed)
    return {name: _leaf(jax.random.fold_in(key, i), shape=shape, kind=kind,
                        dtype=dt or cfg["param_dtype"])
            for i, (name, (shape, kind, dt)) in enumerate(
                param_spec(cfg).items())}


def model_config(cfg):
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer of this family's configurations is an "
                         "expert layer")
    shared, rest = divmod(cfg["shared_expert_intermediate_size"],
                          cfg["moe_intermediate_size"])
    if rest:
        raise ValueError("the shared expert is a whole number of experts' "
                         "widths in the program")
    kinds = layer_types(cfg)
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"], layer_types=kinds,
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=False, rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        qk_norm="head", attn_output_gate=True, block_norm="pre",
        zero_centered_norms=True, ffn_types=("moe",) * len(kinds),
        moe=dict(expert_width=cfg["moe_intermediate_size"],
                 num_experts=router_width(cfg),
                 top_k=cfg["num_experts_per_tok"],
                 shared_experts=shared,
                 norm_topk=cfg["norm_topk_prob"], router="softmax",
                 held=held(cfg), shared_gated=True),
        max_position=cfg["serve"]["cache_len"], dtype=cfg["param_dtype"])


def build_model(cfg, weight_dict):
    """``HybridForCausalLM`` at the configuration's sizes holding
    ``weight_dict``; its own parameters are never materialized."""
    with abstract_parameters():
        model = HybridForCausalLM(model_config(cfg))
    own = dict(model.named_parameters())
    if set(own) != set(weight_dict):
        raise RuntimeError("parameter names differ from the family's spec: "
                           f"{sorted(set(own) ^ set(weight_dict))[:8]}")
    for name, p in own.items():
        w = weight_dict[name]
        if tuple(p.shape) != tuple(w.shape) or str(p.dtype) != str(w.dtype):
            raise RuntimeError(f"{name}: {p.shape} {p.dtype} vs "
                               f"{w.shape} {w.dtype}")
        p.value = w
    return model


#: ``rms_norm_eps`` is part of the preset (``families/olmo_hybrid.py:TINY``
#: says why: at these widths the published 1e-6 swamps the mean square).
#: 8 of the router's 32 experts are held, 4 chosen a token: as at the
#: published sizes, a quarter of the pairs land here.
TINY = {"hidden_size": 64, "rms_norm_eps": 1e-12, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 4,
        "published": {"num_hidden_layers": 48, "num_experts": 32},
        "num_hidden_layers": 4, "vocab_size": 512,
        "max_position_embeddings": 128}
