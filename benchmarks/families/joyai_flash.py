"""JoyAI-LLM-Flash family: the parameter layout, the seeded weights (made
on the device leaf by leaf in the type they are served in), the model
under test (``LatentMoEForCausalLM``) holding them, and the tiny preset of
the CPU rehearsal.  A configuration names this file by ``"family":
"joyai_flash"``."""
import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import weights
# at import, not in the builders: a program without this model (a parent
# commit under this benchmark) fails here at once, before any weight is made
from paddle_tpu.models.latent_moe import (LatentMoEConfig,
                                          LatentMoEForCausalLM)
from paddle_tpu.nn import abstract_parameters

REFERENCE = "joyai_flash"


def param_spec(cfg):
    """``name -> (shape, kind, dtype)``; dtype None is the configuration's
    ``param_dtype``.  ``e_score_correction_bias`` (``mlp.score_bias``) is
    float32 and N(0, 0.02): the published config gives no values."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    spec = {"model.embed": ((cfg["vocab_size"], d), "matrix", None)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.blocks.{i}."
        spec.update({
            p + "norm1.weight": ((d,), "gain", None),
            p + "attn.q_a": ((d, qr), "matrix", None),
            p + "attn.q_norm.weight": ((qr,), "gain", None),
            p + "attn.q_b": ((qr, h * (dn + dr)), "matrix", None),
            p + "attn.kv_a": ((d, kvr + dr), "matrix", None),
            p + "attn.kv_norm.weight": ((kvr,), "gain", None),
            p + "attn.kv_b": ((kvr, h * (dn + dv)), "matrix", None),
            p + "attn.out": ((h * dv, d), "matrix", None),
            p + "norm2.weight": ((d,), "gain", None),
        })
        if i < cfg["first_k_dense_replace"]:
            ff = cfg["intermediate_size"]
            spec.update({p + "mlp.gate": ((d, ff), "matrix", None),
                         p + "mlp.up": ((d, ff), "matrix", None),
                         p + "mlp.down": ((ff, d), "matrix", None)})
        else:
            spec.update({
                p + "mlp.router": ((d, e), "matrix", None),
                p + "mlp.score_bias": ((e,), "bias", "float32"),
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
    w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + w) if kind == "gain" else w).astype(dtype)


def make_weights(cfg, seed):
    """One jitted call a leaf: the float32 normals of one leaf (2.4 GB for
    a layer's stacked expert matrix) are the largest temporary, not those
    of the whole model."""
    key = weights.seed_key(seed)
    return {name: _leaf(jax.random.fold_in(key, i), shape=shape, kind=kind,
                        dtype=dt or cfg["param_dtype"])
            for i, (name, (shape, kind, dt)) in enumerate(
                param_spec(cfg).items())}


def model_config(cfg):
    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        shared_experts=cfg["n_shared_experts"],
        first_dense_layers=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_position=cfg["serve"]["cache_len"], dtype=cfg["param_dtype"])


def build_model(cfg, weight_dict):
    """``LatentMoEForCausalLM`` at the configuration's sizes holding
    ``weight_dict``; its own parameters are never materialized (11 GB of
    them would not fit beside the weights)."""
    with abstract_parameters():
        model = LatentMoEForCausalLM(model_config(cfg))
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


TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "qk_head_dim": 24, "v_head_dim": 16, "head_dim": 8,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "num_experts_per_tok": 4,
        "num_hidden_layers": 3, "vocab_size": 512,
        "max_position_embeddings": 128}
