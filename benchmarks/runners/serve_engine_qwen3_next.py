"""``runners/serve_engine_bf16.py``'s serving run for a model whose layers
are told apart by other named scopes than that runner's own library knows,
as ``serve_engine_hybrid.py`` is: the same ``run()`` (loaded from that file,
not copied), with the one name it reads its ``scope_map`` from bound to
``qwen3_next_lib`` in THIS copy of the module, and the facts this model's
readers need added after it."""
from benchmarks.harness import loader, qwen3_next_lib

_bf16 = loader.load_module("runners", "serve_engine_bf16")
# the loader gives every load a module object of its own: nothing else sees
# this binding.  ``scope_map`` is all that runner asks of the library.
_bf16.latent_moe_lib = qwen3_next_lib


def run(ctx):
    _bf16.run(ctx)
    cfg = ctx.config
    serve = {**cfg["serve"], **(cfg.get("serve_rehearse", {})
                                if ctx.rehearse else {})}
    linear = sum((i + 1) % cfg["full_attention_interval"] != 0
                 for i in range(cfg["num_hidden_layers"]))
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    ctx.facts.update(
        slot_state_bytes=linear * (
            4 * hv * dk * dv + 2 * (cfg["linear_conv_kernel_dim"] - 1)
            * (2 * hk * dk + hv * dv)),
        kv_page_size=serve["kv_page_size"], slots=serve["batch_size"])
