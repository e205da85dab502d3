"""``runners/serve_engine_bf16.py``'s serving run for a model whose layers
are told apart by other named scopes than that runner's own library knows:
the same ``run()`` (loaded from that file, not copied), with the one name it
reads its ``scope_map`` from bound to ``hybrid_lib`` in THIS copy of the
module, and two facts this model's readers need added after it."""
from benchmarks.harness import hybrid_lib, loader

_bf16 = loader.load_module("runners", "serve_engine_bf16")
# the loader gives every load a module object of its own: nothing else sees
# this binding.  ``scope_map`` is all that runner asks of the library.
_bf16.latent_moe_lib = hybrid_lib


def run(ctx):
    _bf16.run(ctx)
    cfg = ctx.config
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    ctx.facts["slot_state_bytes"] = kinds.count("linear_attention") * (
        4 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
        * cfg["linear_value_head_dim"]
        + 2 * (cfg["linear_conv_kernel_dim"] - 1)
        * cfg["linear_num_value_heads"] * (2 * cfg["linear_key_head_dim"]
                                           + cfg["linear_value_head_dim"]))
