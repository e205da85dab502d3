"""The cell ``kimi_linear.longdoc_gen_closed`` on the CPU rehearsal path (the
family's TINY preset: one period, three KDA layers of 4 heads of 16 and a
latent layer, 4 of the router's 16 experts held; the cell's
``rehearse_limits`` lie between the largest of five sound seeds, 0.0058 /
1.2e-4 / 0 — a routing flip of a sigmoid top-4 of 16 sets the widest gap —
and the fp8 control's smallest, 0.125 / 0.0091 / 0.19): a sound run comes
out ``correct: true``; the fp8 control of the reference does not, nor does
a program with one fault in what the configuration added."""
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.harness import kimi_linear_lib as kl
from benchmarks.harness import loader
from paddle_tpu.models import kimi_linear

CELL = "kimi_linear.longdoc_gen_closed"


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
    # slot state: every admission resets a slot's rows, every step reads
    # and writes all of them
    assert deltas["state_bytes_steps"] > 0
    assert deltas["state_slots_reset"] == deltas["admit_rows"] > 0
    assert deltas["gdn_prefill_tokens"] == deltas["admit_tokens"] > 0
    # latent pages beside it: live pages are counted as any model's
    assert 0 < deltas["kv_pages_live_steps"] < deltas["kv_page_slots_steps"]
    # 4 slots x 4 choices x 3 expert layers a step, about a quarter of them
    # on the 4 held of 16 experts
    assert deltas["moe_pairs_routed"] == deltas["moe_layer_steps"] * 16 > 0
    assert deltas["moe_pairs_local"] == deltas["moe_routed_tokens"]
    assert 0.15 < deltas["moe_pairs_local"] / deltas["moe_pairs_routed"] < 0.4
    routed = next(l for l in lines if "expert_routed_tokens" in l)
    assert len(routed["expert_routed_tokens"]) == 4
    control = next(l for l in lines if "control_correct" in l)
    assert control == {"control_correct": False, "control_mode": "fp8"}
    assert not checks["control.max_gap"]["ok"]
    assert not checks["control.mean_gap"]["ok"]
    assert not checks["control.deep_gap_share"]["ok"]


def _one_decay_a_head(mp):
    real = kimi_linear.KimiDeltaAttention._gates

    def gates(self, x, valid):
        g, beta = real(self, x, valid)
        return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta

    mp.setattr(kimi_linear.KimiDeltaAttention, "_gates", gates)


def _state_not_reset(mp):
    """An admission that starts from what the slot's last tenant left."""
    real = kimi_linear.KimiDeltaAttention.admit

    def admit(self, x, positions, kv, rows):
        out, new = real(self, x, positions, kv, rows)
        return out, {**new, "state": new["state"] + kv["state"]}

    mp.setattr(kimi_linear.KimiDeltaAttention, "admit", admit)


@pytest.mark.parametrize("fault", [_one_decay_a_head, _state_not_reset])
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
    # a subset of the issue's 22 (PERF.md section 7 says which and why):
    # a later PR may add the rest without an edit here
    assert names and all(n.endswith(".kml") for n in names)
    for n in names:   # every reader is a file that loads, and finds nothing
        read = loader.load_module("layer_metrics", n).read   # in an empty run
        assert callable(read) and read({"facts": {}, "peaks": {}}) is None
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in layer)
    assert {"decode_step_ms.kml", "admit_call_ms.kml",
            "host_ms_per_step.kml", "device_idle_share.kml",
            "kda_time_share.kml", "mla_time_share.kml", "moe_time_share.kml",
            "kda_chunk_roofline_share.kml", "kda_step_roofline_share.kml",
            "moe_gated_mlp_tm16_roofline_share.kml",
            "moe_gated_mlp_tm128_roofline_share.kml",
            "experts_touched_per_step.kml"} <= names
    # the other cells keep their own readers and gain none
    for cell, suffix in (("joyai_flash.ragdocs_closed", ".rag"),
                         ("olmo_hybrid.ragdocs_closed", ".hyb"),
                         ("qwen3_next.longgen_closed", ".qnx"),
                         ("k_exaone.ragdocs_closed", ".kex")):
        _, theirs = loader.metrics_of(cell, man)
        assert theirs and not {m["name"] for m in theirs} & names
        assert all(m["name"].endswith(suffix) for m in theirs)
    cell, config, traffic = loader.load_cell(CELL)
    # the issue's traffic, letter for letter
    assert (traffic["loop"], traffic["clients"], traffic["pool"],
            traffic["warm_seconds"], traffic["schedule_seed"]) == (
                "closed", 256, 512, 30, 0)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 1024,
                                     "max": 4096}
    assert traffic["output_len"] == {"dist": "uniform", "min": 256,
                                     "max": 768}
    assert traffic["prompt_buckets"] == [1536, 2048, 3072, 4096]
    serve = config["serve"]
    assert (serve["batch_size"], serve["cache_len"], serve["kv_page_size"],
            serve["speculative_k"]) == (128, 4864, 16, 0)
    assert serve["cache_len"] == (traffic["prompt_len"]["max"]
                                  + traffic["output_len"]["max"])
    assert cell["check_requests"] >= 24 and cell["chips"] == 1
    assert cell["runner"] == "serve_engine_kimi_linear"
    entry = next(c for c in man["configs"] if c["name"] == "kimi_linear_serve")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert len([w for w in man["workloads"] if w["chips"] == 4]) == 0


def test_the_configuration_holds_every_number_of_the_catalog_row():
    """The catalog row's ``config`` as the issue quotes it; every key but
    the three in ``reduced`` is the published one."""
    _, config, _ = loader.load_cell(CELL)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_max_length": 1048576, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840}
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value != config[key]
        else:
            assert config[key] == value, key
    assert set(config["assumed"]) >= {"kda", "mla", "moe", "A_log_dt_bias",
                                      "e_score_correction_bias", "weights",
                                      "param_dtype", "serve"}
    assert "16 v5e chips" in config["deployment"]


def test_the_kernel_counts():
    # per chunk and head 2 C dv (3 dk + C) FLOPs, as the scalar-decay walk
    assert kl.chunk_flops(64, 1, 128, 128) == 2 * 64 * 128 * (3 * 128 + 64)
    # float32: three dk operands, U, a row of P and o a token; one row of
    # decays a chunk; the final state a row
    assert kl.chunk_bytes(64, 1, 1, 128, 128) == 4 * (
        64 * (3 * 128 + 2 * 128 + 64) + 128 + 128 * 128)
    assert kl.step_flops(128, 32, 128, 128) == 7 * 128 * 32 * 128 * 128
    # the issue's count, 2 x 32 x 128 x 128 x 4 B a slot and layer, and
    # the six rows
    assert kl.step_bytes(1, 32, 128, 128) == (
        2 * 32 * 128 * 128 * 4 + 4 * 32 * 6 * 128)
    ev = {"facts": {"counters": {"admit_steps": 10, "admit_rows": 10,
                                 "admit_tokens": 25600, "decode_steps": 100,
                                 "gdn_prefill_tokens": 25600,
                                 "state_bytes_steps": 100 * 2 * 128 * 1000},
                    "sizes": {"num_attention_heads": 32,
                              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                              "v_head_dim": 128, "hidden_size": 2304,
                              "moe_intermediate_size": 1024,
                              "num_experts": 64, "num_experts_per_token": 8},
                    "kda": {"heads": 32, "dk": 128, "dv": 128},
                    "slot_state_bytes": 1000, "prompt_pairs_mean": 3e6},
          "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    # no trace: nothing to read, and no raise
    for read in (kl.chunk_kernel_roofline_share,
                 kl.step_kernel_roofline_share, kl.kda_time_share,
                 kl.mla_time_share, kl.moe_time_share,
                 kl.decode_expert_kernel_roofline_share,
                 kl.admit_expert_kernel_roofline_share):
        assert read(ev) is None
        assert read({"facts": {}, "peaks": {}}) is None
    sizes = kl._renamed(ev)["facts"]["sizes"]
    assert sizes["num_experts_per_tok"] == 8
    assert "num_experts_per_tok" not in ev["facts"]["sizes"]
    assert kl.classify("jit(pstep)/moe/dot_general") == "moe"
    assert kl.classify("jit(pstep)/kda/mul") == "kda"
    assert kl.classify("jit(padmit)/mla/while/body/dot") == "mla"
    assert kl.classify("jit(padmit)/take") is None
    texts = {"step": """
  %fusion.3 = bf16[128,2304]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(pstep)/mla/dot_general" source_file="x.py"}
  ROOT %kda_step.1 = f32[129,32,128,128]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(pstep)/kda/pallas_call"}
  %fusion.9 = bf16[129,3,12288]{2,1,0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(pstep)/kda/dynamic_update_slice"}
""", "admit[4096]": """
  %fusion.3 = bf16[128,2304]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/moe/add"}
"""}
    scopes = kl.scope_map(texts)
    # fusion.3 names a different mechanism in the two programs: left out
    assert scopes == {
        "%kda_step.1 = f32[129,32,128,128]{3,2,1,0}": "kda",
        "%fusion.9 = bf16[129,3,12288]{2,1,0}": "kda"}
    ev = {"facts": {"op_scopes": scopes}}
    assert kl._kind(ev, "%fusion.9 = bf16[129,3,12288]{2,1,0} fusion(%p.2)"
                    ) == "kda"
    assert kl._kind(ev, "%kda_chunk.7 = f32[2] custom-call()") == "kda"
    assert kl._kind(ev, "%latent_prefill_attention.2 = bf16[2] custom-call()"
                    ) == "mla"
    assert kl._kind(ev, "%moe_gated_mlp_tm16.1 = bf16[2] custom-call()"
                    ) == "moe"
    assert kl._kind(ev, "%fusion.1 = f32[16]{0} fusion(%p.2)") is None
