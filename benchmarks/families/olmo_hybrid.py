"""Olmo-Hybrid family: the parameter layout, the seeded weights (made on the
device leaf by leaf in the type they are served in), the model under test
(``HybridForCausalLM``) holding them, and the tiny preset of the CPU
rehearsal.  A configuration names this file by ``"family": "olmo_hybrid"``."""
import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import weights
# at import, not in the builders: a program without this model (a parent
# commit under this benchmark) fails here at once, before any weight is made
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.nn import abstract_parameters

REFERENCE = "olmo_hybrid"


def layer_types(cfg):
    """The kinds of the layers as run: the first ``num_hidden_layers``
    entries of the published list, which the configuration copies whole."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def param_spec(cfg):
    """``name -> (shape, kind, dtype)``; dtype None is the configuration's
    ``param_dtype``.  The program's layout fuses what one matmul can
    compute: ``qkv`` is ``[W_q | W_k | W_v]`` and ``ab`` is ``[W_a | W_b]``,
    side by side.  ``A_log`` and ``dt_bias`` are float32, as the decay."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = h * (2 * dk + dv)
    spec = {"model.embed": ((cfg["vocab_size"], d), "matrix", None)}
    for i, kind in enumerate(layer_types(cfg)):
        p = f"model.blocks.{i}."
        if kind == "full_attention":
            spec.update({
                p + "mixer.qkv": ((d, 3 * d), "matrix", None),
                p + "mixer.q_norm.weight": ((d,), "gain", None),
                p + "mixer.k_norm.weight": ((d,), "gain", None),
                p + "mixer.out": ((d, d), "matrix", None)})
        else:
            spec.update({
                p + "mixer.qkv": ((d, width), "matrix", None),
                p + "mixer.gate": ((d, h * dv), "matrix", None),
                p + "mixer.ab": ((d, 2 * h), "matrix", None),
                p + "mixer.A_log": ((h,), "a_log", "float32"),
                p + "mixer.dt_bias": ((h,), "dt_bias", "float32"),
                p + "mixer.conv": ((cfg["linear_conv_kernel_dim"], width),
                                   "matrix", None),
                p + "mixer.o_norm.weight": ((dv,), "gain", None),
                p + "mixer.out": ((h * dv, d), "matrix", None)})
        spec.update({
            p + "norm1.weight": ((d,), "gain", None),
            p + "mlp.gate": ((d, f), "matrix", None),
            p + "mlp.up": ((d, f), "matrix", None),
            p + "mlp.down": ((f, d), "matrix", None),
            p + "norm2.weight": ((d,), "gain", None)})
    spec.update({"model.norm_f.weight": ((d,), "gain", None),
                 "head": ((d, cfg["vocab_size"]), "matrix", None)})
    return spec


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _leaf(key, *, shape, kind, dtype):
    """Matrices (the conv taps among them) N(0, 0.02), gains 1 + N(0, 0.02);
    ``A_log = log U(1, 16)`` and ``dt_bias = softplus^-1(U(0.001, 0.1))``,
    Mamba-2's initialisation of the decay."""
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
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("key and value heads of the linear layers differ: "
                         "the program has one head count for both")
    return HybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        layer_types=layer_types(cfg),
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
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


#: ``rms_norm_eps`` is part of the preset: with N(0, 0.02) weights at these
#: widths every activation of the first layers is under 1e-3, where the
#: published 1e-6 swamps the mean square and the norms pass next to nothing
#: on; the mixers then hardly move the logits and a fault in one cannot be
#: seen.  At the published widths the activations are a hundred times larger.
TINY = {"hidden_size": 64, "rms_norm_eps": 1e-12, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "num_hidden_layers": 4, "vocab_size": 512,
        "max_position_embeddings": 128,
        "layer_types": ["linear_attention", "linear_attention",
                        "linear_attention", "full_attention"]}
