"""The cell ``qwen3_next.longgen_closed`` on the CPU rehearsal path (the
family's TINY preset: 8 of the router's 32 experts held; the cell's
``rehearse_limits``, 3 x the largest of four sound seeds 0.0092 / 7.0e-5 / 0,
the fp8 control's smallest 0.096 / 0.0037 / 0.078): a sound run comes out
``correct: true``; the fp8 control of the reference does not, nor does a
program with one fault in what the configuration added."""
import importlib
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.harness import loader
from benchmarks.harness import qwen3_next_lib as ql
from paddle_tpu.models import hybrid

CELL = "qwen3_next.longgen_closed"
gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")


def _run(capsys, seed, *extra):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(loader.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    capsys.readouterr()
    rc = mod.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2",
                   "--trace", "0", "--rehearse", *extra])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert rc == 0
    return lines, {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_sound_run_is_correct_and_the_fp8_control_is_not(capsys, seed):
    lines, checks = _run(capsys, seed, "--control")
    last = lines[-1]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["metrics"] == {} and last["attempted"] > 0
    deltas = next(l for l in lines if "counter_deltas" in l)["counter_deltas"]
    assert deltas["state_slots_reset"] == deltas["admit_rows"] > 0
    assert deltas["state_bytes_steps"] > 0
    # slot state AND experts in one engine: 4 slots x 4 choices x 4 layers a
    # step, about a quarter of them on the 8 held of 32 experts
    # (harvested a step behind: counted by the layers harvested)
    assert deltas["moe_pairs_routed"] == deltas["moe_layer_steps"] * 16 > 0
    assert deltas["moe_pairs_local"] == deltas["moe_routed_tokens"]
    assert 0.15 < deltas["moe_pairs_local"] / deltas["moe_pairs_routed"] < 0.4
    routed = next(l for l in lines if "expert_routed_tokens" in l)
    assert len(routed["expert_routed_tokens"]) == 8
    control = next(l for l in lines if "control_correct" in l)
    assert control == {"control_correct": False, "control_mode": "fp8"}
    assert not checks["control.mean_gap"]["ok"]
    assert not checks["control.deep_gap_share"]["ok"]


def _rotary_on_every_dim(mp):
    real = hybrid.rope_rotate_half
    mp.setattr(hybrid, "rope_rotate_half",
               lambda x, pos, theta, dims: real(x, pos, theta, x.shape[-1]))


def _absent_pair_computed_by_expert_e_mod_held(mp):
    real = gm.ragged_layout

    def layout(ids, groups, tile_m, partial=False):
        lay = real(jnp.mod(ids, groups), groups, tile_m)
        return {**lay, "present": jnp.ones(ids.shape, bool)}

    mp.setattr(gm, "ragged_layout", layout)


@pytest.mark.parametrize("fault", [_rotary_on_every_dim,
                                   _absent_pair_computed_by_expert_e_mod_held])
def test_a_program_with_one_fault_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    lines, checks = _run(capsys, 2 ** 31 + 11)
    assert lines[-1]["correct"] is False
    assert not checks["mean_gap"]["ok"]


def test_the_manifest_names_the_cell_and_its_readers():
    man = loader.manifest()
    e2e, layer = loader.metrics_of(CELL, man)
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in layer}
    assert names and all(n.endswith(".qnx") for n in names)
    for n in names:   # every reader is a file that loads, and finds nothing
        read = loader.load_module("layer_metrics", n).read   # in an empty run
        assert callable(read) and read({"facts": {}, "peaks": {}}) is None
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in layer)
    # the other cells keep their own readers and gain none
    for cell, suffix in (("joyai_flash.ragdocs_closed", ".rag"),
                         ("olmo_hybrid.ragdocs_closed", ".hyb")):
        _, theirs = loader.metrics_of(cell, man)
        assert theirs and not {m["name"] for m in theirs} & names
        assert all(m["name"].endswith(suffix) for m in theirs)
    cell, config, traffic = loader.load_cell(CELL)
    assert (config["serve"]["batch_size"], traffic["clients"],
            traffic["warm_seconds"]) == (64, 128, 20)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 128,
                                     "max": 1024}
    assert traffic["output_len"] == {"dist": "uniform", "min": 256,
                                     "max": 1024}
    assert cell["check_requests"] >= 24 and cell["chips"] == 1


def test_the_configuration_carries_the_published_widths():
    cfg = loader.load_json("configs", "qwen3_next_serve.json")
    want = {"hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "head_dim": 256,
            "partial_rotary_factor": 0.25, "rope_theta": 10000000,
            "full_attention_interval": 4, "linear_num_key_heads": 16,
            "linear_num_value_heads": 32, "linear_key_head_dim": 128,
            "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
            "moe_intermediate_size": 512, "num_experts_per_tok": 10,
            "shared_expert_intermediate_size": 512, "vocab_size": 151936,
            "intermediate_size": 5120, "decoder_sparse_step": 1,
            "max_position_embeddings": 262144, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (8, 128)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512}
    for key in ("assumed", "deployment", "reduced_why"):
        assert cfg[key]
    fam = loader.load_module("families", "qwen3_next")
    assert fam.held(cfg) == (0, 128) and fam.router_width(cfg) == 512
    spec = fam.param_spec(cfg)

    def count(prefix):
        n = 0
        for name, (shape, _, _) in spec.items():
            if name.startswith(prefix):
                size = 1
                for s in shape:
                    size *= s
                n += size
        return n

    # the issue's counts: a linear mixer 33.72 M, a full mixer 27.26 M, an
    # expert 3.146 M (128 of them held a layer), embedding + head 622.3 M
    assert round(count("model.blocks.0.mixer.") / 1e6, 2) == 33.72
    assert round(count("model.blocks.3.mixer.") / 1e6, 2) == 27.26
    assert count("model.blocks.0.mlp.expert_") == 128 * 3 * 2048 * 512
    assert round(3 * 2048 * 512 / 1e6, 3) == 3.146
    assert spec["model.blocks.0.mlp.router"][0] == (2048, 512)
    assert round((count("model.embed") + count("head")) / 1e6, 1) == 622.3
    assert round(2 * count("") / 1e9, 2) == 8.27


def test_the_kernel_counts():
    # the state kernel: 32 value heads' [128, 128] float32 state in and out,
    # q and k of the 16 KEY heads, v, two gates and the output of the 32
    assert ql.step_flops(64, 32, 128, 128) == 7 * 64 * 32 * 128 * 128
    assert ql.step_bytes(1, 16, 32, 128, 128) == 4 * (
        32 * (2 * 128 * 128 + 2 * 128 + 2) + 16 * 2 * 128)
    # paged_decode at the decode width: a swept key costs a [16, 512] x
    # [512] score product and a [16] x [512] context product, and its K and
    # V rows of 512 bfloat16 lanes
    assert ql.paged_decode_flops(1000, 16, 512) == 4 * 1000 * 16 * 512
    assert ql.paged_decode_bytes(1000, 64, 16, 512) == 2 * (
        2 * 1000 * 512 + 2 * 64 * 16 * 512)
    ev = {"facts": {"counters": {"moe_pairs_routed": 5120,
                                 "moe_pairs_local": 1290}}, "peaks": {}}
    assert round(ql.moe_local_pair_share(ev), 2) == 25.2
    for read in (ql.moe_local_pair_share, ql.step_kernel_roofline_share,
                 ql.paged_decode_roofline_share,
                 ql.admit_expert_kernel_roofline_share,
                 ql.decode_expert_kernel_roofline_share,
                 ql.chunk_kernel_roofline_share, ql.moe_time_share,
                 ql.gdn_time_share, ql.full_attn_time_share):
        assert read({"facts": {}, "peaks": {}}) is None
    assert ql.classify("jit(pstep)/moe/dot_general") == "moe"
    assert ql.classify("jit(pstep)/gdn/mul") == "gdn"
    assert ql.classify("jit(padmit)/attn/while/body/dot") == "attn"
    assert ql.classify("jit(padmit)/take") is None
    texts = {"step": """
  %fusion.3 = bf16[64,2048]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(pstep)/attn/dot_general" source_file="x.py"}
  ROOT %moe_gated_mlp_tm16.1 = bf16[2688,2048]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(pstep)/moe/pallas_call"}
  %fusion.9 = f32[64]{0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(pstep)/gdn/mul"}
""", "admit[256]": """
  %fusion.3 = bf16[64,2048]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/moe/add"}
"""}
    scopes = ql.scope_map(texts)
    # fusion.3 names a different mechanism in the two programs: left out
    assert scopes == {
        "%moe_gated_mlp_tm16.1 = bf16[2688,2048]{1,0}": "moe",
        "%fusion.9 = f32[64]{0}": "gdn"}
    ev = {"facts": {"op_scopes": scopes}}
    assert ql._kind(ev, "%fusion.9 = f32[64]{0} fusion(%p.2)") == "gdn"
    assert ql._kind(ev, "%paged_decode.7 = bf16[2] custom-call()") == "attn"
    assert ql._kind(ev, "%gated_delta_step.2 = f32[2] custom-call()") == "gdn"
    assert ql._kind(ev, "%fusion.1 = f32[16]{0} fusion(%p.2)") is None
