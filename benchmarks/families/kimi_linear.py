"""Kimi Linear family: the parameter layout, the seeded weights (made on the
device leaf by leaf in the type they are served in), the model under test
(``paddle_tpu.models.kimi_linear``) holding them, and the tiny preset of the
CPU rehearsal.  A configuration names this file by ``"family":
"kimi_linear"``.

One chip's share of a layer: the configuration's ``num_experts`` counts the
experts HELD here (``expert_offset`` is the first of them),
``published.num_experts`` the router's outputs, and ``vocab_size`` the rows
of the embedding and the head that are here.  ``linear_attn_config`` is the
source's whole group; layer ``i`` (1-based, as its lists are) is a latent
layer where ``full_attn_layers`` names it, a KDA layer elsewhere, and the
first ``num_hidden_layers`` are built."""
import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import weights
# at import, not in the builders: a program without this model (a parent
# commit under this benchmark) fails here at once, before any weight is made
from paddle_tpu.models.kimi_linear import (KimiLinearConfig,
                                           KimiLinearForCausalLM)
from paddle_tpu.nn import abstract_parameters

REFERENCE = "kimi_linear"


def layer_types(cfg):
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return tuple("mla" if i + 1 in full else "kda"
                 for i in range(cfg["num_hidden_layers"]))


def ffn_types(cfg):
    """``dense`` for the first ``first_k_dense_replace`` layers, ``moe``
    after them (``moe_layer_freq`` 1)."""
    return tuple("dense" if i < cfg["first_k_dense_replace"] else "moe"
                 for i in range(cfg["num_hidden_layers"]))


def held(cfg):
    """``(first, count)`` of the experts this chip holds."""
    return int(cfg.get("expert_offset", 0)), int(cfg["num_experts"])


def router_width(cfg):
    return int(cfg["published"]["num_experts"])


def param_spec(cfg):
    """``name -> (shape, kind, dtype)``; dtype None is the configuration's
    ``param_dtype``.  A KDA layer's ``mixer.qkv`` is ``[W_q | W_k | W_v]``
    (what the convolution runs over), ``f_a``/``f_b`` and ``g_a``/``g_b``
    the two low-rank gates, ``b`` the ``beta`` projection;
    ``e_score_correction_bias`` (``mlp.score_bias``) is float32 over the
    router's whole width."""
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    lh, ld, K = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, f = held(cfg)[1], cfg["moe_intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    spec = {"model.embed": ((cfg["vocab_size"], d), "matrix", None)}
    for i, (kind, ffn) in enumerate(zip(layer_types(cfg), ffn_types(cfg))):
        p = f"model.blocks.{i}."
        spec[p + "norm1.weight"] = ((d,), "gain", None)
        if kind == "kda":
            spec.update({
                p + "mixer.qkv": ((d, 3 * lh * ld), "matrix", None),
                p + "mixer.conv": ((K, 3 * lh * ld), "matrix", None),
                p + "mixer.f_a": ((d, ld), "matrix", None),
                p + "mixer.f_b": ((ld, lh * ld), "matrix", None),
                p + "mixer.b": ((d, lh), "matrix", None),
                p + "mixer.A_log": ((lh,), "a_log", "float32"),
                p + "mixer.dt_bias": ((lh * ld,), "dt_bias", "float32"),
                p + "mixer.g_a": ((d, ld), "matrix", None),
                p + "mixer.g_b": ((ld, lh * ld), "matrix", None),
                p + "mixer.o_norm.weight": ((ld,), "gain", None),
                p + "mixer.out": ((lh * ld, d), "matrix", None)})
        else:
            spec.update({
                p + "mixer.q": ((d, h * (dn + dr)), "matrix", None),
                p + "mixer.kv_a": ((d, r + dr), "matrix", None),
                p + "mixer.kv_norm.weight": ((r,), "gain", None),
                p + "mixer.kv_b": ((r, h * (dn + dv)), "matrix", None),
                p + "mixer.out": ((h * dv, d), "matrix", None)})
        spec[p + "norm2.weight"] = ((d,), "gain", None)
        if ffn == "dense":
            w = cfg["intermediate_size"]
            spec.update({p + "mlp.gate": ((d, w), "matrix", None),
                         p + "mlp.up": ((d, w), "matrix", None),
                         p + "mlp.down": ((w, d), "matrix", None)})
        else:
            spec.update({
                p + "mlp.router": ((d, router_width(cfg)), "matrix", None),
                p + "mlp.score_bias": ((router_width(cfg),), "bias",
                                       "float32"),
                p + "mlp.expert_gate": ((e, d, f), "matrix", None),
                p + "mlp.expert_up": ((e, d, f), "matrix", None),
                p + "mlp.expert_down": ((e, f, d), "matrix", None),
                p + "mlp.shared_gate": ((d, fs), "matrix", None),
                p + "mlp.shared_up": ((d, fs), "matrix", None),
                p + "mlp.shared_down": ((fs, d), "matrix", None)})
    spec.update({"model.norm_f.weight": ((d,), "gain", None),
                 "head": ((d, cfg["vocab_size"]), "matrix", None)})
    return spec


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _leaf(key, *, shape, kind, dtype):
    """Matrices (the conv taps among them) N(0, 0.02), gains 1 + N(0, 0.02),
    the score bias zero; ``A_log = log U(1, 16)`` a head and ``dt_bias =
    softplus^-1(U(0.001, 0.1))`` a channel (the configuration's ``assumed``
    says why each)."""
    if kind == "bias":
        return jnp.zeros(shape, dtype)
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
    if (cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["num_expert_group"] != 1 or not cfg["mla_use_nope"]
            or cfg["q_lora_rank"] is not None):
        raise ValueError("this family routes by sigmoid scores with no group "
                         "limit and attends through NoPE latent attention "
                         "without a query bottleneck")
    lin, ffns = cfg["linear_attn_config"], ffn_types(cfg)
    return KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=layer_types(cfg), ffn_types=ffns,
        intermediate_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], linear_num_heads=lin["num_heads"],
        linear_head_dim=lin["head_dim"],
        linear_conv_kernel=lin["short_conv_kernel_size"],
        moe=dict(expert_width=cfg["moe_intermediate_size"],
                 num_experts=router_width(cfg),
                 top_k=cfg["num_experts_per_token"],
                 shared_experts=cfg["num_shared_experts"],
                 routed_scale=cfg["routed_scaling_factor"],
                 norm_topk=cfg["moe_renormalize"], router="sigmoid",
                 held=held(cfg)) if "moe" in ffns else None,
        rms_norm_eps=cfg["rms_norm_eps"],
        max_position=cfg["serve"]["cache_len"], dtype=cfg["param_dtype"])


def build_model(cfg, weight_dict):
    """``KimiLinearForCausalLM`` at the configuration's sizes holding
    ``weight_dict``; its own parameters are never materialized."""
    with abstract_parameters():
        model = KimiLinearForCausalLM(model_config(cfg))
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
#: says why: at these widths the published 1e-5 swamps the mean square).
#: One period: three KDA layers of 4 heads of 16 and a latent layer; 4 of
#: the router's 16 experts are held, 4 chosen a token.
TINY = {"hidden_size": 64, "rms_norm_eps": 1e-12, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_token": 4,
        "linear_attn_config": {"full_attn_layers": [4, 8],
                               "kda_layers": [1, 2, 3, 5, 6, 7],
                               "num_heads": 4, "head_dim": 16,
                               "short_conv_kernel_size": 4},
        "published": {"num_hidden_layers": 27, "num_experts": 16,
                      "vocab_size": 163840},
        "num_hidden_layers": 4, "vocab_size": 512}
