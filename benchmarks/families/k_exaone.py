"""K-EXAONE family: the parameter layout, the seeded weights (made on the
device leaf by leaf in the type they are served in), the model under test
(``HybridForCausalLM`` with this family's options) holding them, and the
tiny preset of the CPU rehearsal.  A configuration names this file by
``"family": "k_exaone"``.

One chip's share of a layer: the configuration's ``num_experts`` counts the
experts HELD here (``expert_offset`` is the first of them),
``published.num_experts`` the router's outputs, and ``vocab_size`` the rows
of the embedding and the head that are here.  ``layer_types`` and
``mlp_layer_types`` are the source's whole lists; the first
``num_hidden_layers`` entries are built."""
import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import weights
# at import, not in the builders: a program without a window layer (a parent
# commit under this benchmark) fails here at once, before any weight is made
from paddle_tpu.models.hybrid import (HybridConfig, HybridForCausalLM,
                                      ring_positions)  # noqa: F401
from paddle_tpu.nn import abstract_parameters

REFERENCE = "k_exaone"


def layer_types(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def ffn_types(cfg):
    """``dense`` or ``moe`` a layer, from ``mlp_layer_types``' ``dense`` /
    ``sparse``."""
    return tuple("dense" if t == "dense" else "moe"
                 for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])


def held(cfg):
    """``(first, count)`` of the experts this chip holds."""
    return int(cfg.get("expert_offset", 0)), int(cfg["num_experts"])


def router_width(cfg):
    return int(cfg["published"]["num_experts"])


def param_spec(cfg):
    """``name -> (shape, kind, dtype)``; dtype None is the configuration's
    ``param_dtype``.  ``mixer.qkv`` is ``[W_q | W_k | W_v]`` of both
    attention kinds; ``e_score_correction_bias`` (``mlp.score_bias``) is
    float32 over the router's whole width."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = held(cfg)[1], cfg["moe_intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    spec = {"model.embed": ((cfg["vocab_size"], d), "matrix", None)}
    for i, ffn in enumerate(ffn_types(cfg)):
        p = f"model.blocks.{i}."
        spec.update({
            p + "mixer.qkv": ((d, (h + 2 * hkv) * hd), "matrix", None),
            p + "mixer.q_norm.weight": ((hd,), "gain", None),
            p + "mixer.k_norm.weight": ((hd,), "gain", None),
            p + "mixer.out": ((h * hd, d), "matrix", None),
            p + "norm1.weight": ((d,), "gain", None)})
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
        spec[p + "norm2.weight"] = ((d,), "gain", None)
    spec.update({"model.norm_f.weight": ((d,), "gain", None),
                 "head": ((d, cfg["vocab_size"]), "matrix", None)})
    return spec


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _leaf(key, *, shape, kind, dtype):
    """Matrices N(0, 0.02), gains 1 + N(0, 0.02), the score bias zero (the
    configuration's ``assumed`` says why)."""
    if kind == "bias":
        return jnp.zeros(shape, dtype)
    w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + w) if kind == "gain" else w).astype(dtype)


def make_weights(cfg, seed):
    key = weights.seed_key(seed)
    return {name: _leaf(jax.random.fold_in(key, i), shape=shape, kind=kind,
                        dtype=dt or cfg["param_dtype"])
            for i, (name, (shape, kind, dt)) in enumerate(
                param_spec(cfg).items())}


def model_config(cfg):
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1:
        raise ValueError("this family routes by sigmoid scores with no "
                         "group limit")
    kinds, ffns = layer_types(cfg), ffn_types(cfg)
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"], layer_types=kinds,
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        # the global layers carry no positions
        rope_kinds=("sliding_attention",),
        sliding_window=cfg["sliding_window"], qk_norm="head",
        block_norm="post", ffn_types=ffns,
        moe=dict(expert_width=cfg["moe_intermediate_size"],
                 num_experts=router_width(cfg),
                 top_k=cfg["num_experts_per_tok"],
                 shared_experts=cfg["num_shared_experts"],
                 routed_scale=cfg["routed_scaling_factor"],
                 norm_topk=cfg["norm_topk_prob"], router="sigmoid",
                 held=held(cfg)) if "moe" in ffns else None,
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
#: says why: at these widths the published 1e-5 swamps the mean square).
#: A window of 8 so that the rehearsal's prompts of 16-48 wrap the ring; 4
#: of the router's 16 experts are held, 4 chosen a token.
TINY = {"hidden_size": 64, "rms_norm_eps": 1e-12, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 4, "sliding_window": 8,
        "published": {"num_hidden_layers": 48, "num_experts": 16,
                      "vocab_size": 153600, "num_nextn_predict_layers": 1},
        "num_hidden_layers": 4, "vocab_size": 512,
        "max_position_embeddings": 128}
