"""``runners/serve_engine_bf16.py``'s serving run for a model whose layers
are told apart by other named scopes than that runner's own library knows,
as ``serve_engine_qwen3_next.py`` is: the same ``run()`` (loaded from that
file, not copied), with the one name it reads its ``scope_map`` from bound
to ``kimi_linear_lib`` in THIS copy of the module, and the facts this
model's readers need added after it."""
from benchmarks.harness import kimi_linear_lib, loader

_bf16 = loader.load_module("runners", "serve_engine_bf16")
# the loader gives every load a module object of its own: nothing else sees
# this binding.  ``scope_map`` is all that runner asks of the library.
_bf16.latent_moe_lib = kimi_linear_lib


def run(ctx):
    _bf16.run(ctx)
    cfg = ctx.config
    lin = cfg["linear_attn_config"]
    h, d, taps = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    kda_layers = sum(i <= cfg["num_hidden_layers"] for i in lin["kda_layers"])
    ctx.facts.update(
        # the KDA sizes lie in a nested group, which ``sizes`` leaves out
        kda={"heads": h, "dk": d, "dv": d},
        # a slot's float32 states and its bfloat16 conv windows
        slot_state_bytes=kda_layers * (4 * h * d * d
                                       + 2 * (taps - 1) * 3 * h * d))
