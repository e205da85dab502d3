"""``runners/serve_engine_bf16.py``'s serving run for a model whose layers
are told apart by other named scopes than that runner's own library knows,
as ``serve_engine_qwen3_next.py`` is: the same ``run()`` (loaded from that
file, not copied), with the one name it reads its ``scope_map`` from bound
to ``k_exaone_lib`` in THIS copy of the module, and the facts this model's
readers need added after it."""
import numpy as np

from benchmarks.harness import k_exaone_lib, loader

_bf16 = loader.load_module("runners", "serve_engine_bf16")
# the loader gives every load a module object of its own: nothing else sees
# this binding.  ``scope_map`` is all that runner asks of the library.
_bf16.latent_moe_lib = k_exaone_lib


def run(ctx):
    _bf16.run(ctx)
    cfg = ctx.config
    serve = {**cfg["serve"], **(cfg.get("serve_rehearse", {})
                                if ctx.rehearse else {})}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]   # bfloat16 bytes
    # the same seed gives the same requests: the mix's mean of the pairs a
    # window layer defines over a prompt
    reqs = ctx.generator.generate(ctx.traffic, cfg, ctx.seed, ctx.seconds)
    ctx.facts.update(
        slot_state_bytes=kinds.count("sliding_attention") * 2
        * cfg["sliding_window"] * row,
        window_pairs_mean=float(np.mean([k_exaone_lib.window_pairs(
            len(r["prompt"]), cfg["sliding_window"]) for r in reqs])),
        kv_page_size=serve["kv_page_size"], slots=serve["batch_size"])
