"""The cell ``k_exaone.ragdocs_closed`` on the CPU rehearsal path (the
family's TINY preset: a window of 8 under prompts of 20-48, 4 of the
router's 16 experts held; the cell's ``rehearse_limits`` lie between the
largest of nine sound seeds, 0.071 / 0.0032 / 0.057 — a routing flip of a
sigmoid top-4 of 16 sets the widest gap, so ``max_gap`` has 3 x of room and
does not tell —, and the fp8 control's smallest, 0.071 / 0.0088 / 0.17): a
sound run comes out ``correct: true``; the fp8 control of the reference does
not, nor does a program with one fault in what the configuration added."""
import importlib.util
import json
import os

import pytest

from benchmarks.harness import k_exaone_lib as kl
from benchmarks.harness import loader
from paddle_tpu.models import hybrid

CELL = "k_exaone.ragdocs_closed"


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
    # the rings are slot state: counted with the state's counters, reset by
    # nobody (an admission overwrites what it needs)
    assert deltas["state_bytes_steps"] > 0
    assert deltas["gdn_prefill_tokens"] == deltas["admit_tokens"] > 0
    # rings AND experts in one engine: 4 slots x 4 choices x 3 expert layers
    # a step, about a quarter of them on the 4 held of 16 experts
    assert deltas["moe_pairs_routed"] == deltas["moe_layer_steps"] * 16 > 0
    assert deltas["moe_pairs_local"] == deltas["moe_routed_tokens"]
    assert 0.15 < deltas["moe_pairs_local"] / deltas["moe_pairs_routed"] < 0.4
    routed = next(l for l in lines if "expert_routed_tokens" in l)
    assert len(routed["expert_routed_tokens"]) == 4
    control = next(l for l in lines if "control_correct" in l)
    assert control == {"control_correct": False, "control_mode": "fp8"}
    assert not checks["control.mean_gap"]["ok"]
    assert not checks["control.deep_gap_share"]["ok"]


def _window_one_key_short(mp):
    real = hybrid.key_visible
    mp.setattr(hybrid, "key_visible",
               lambda kp, qp, window: real(kp, qp, window - 1))


def _window_layers_not_rotated(mp):
    real = hybrid.SlidingAttention.__init__

    def init(self, cfg):
        real(self, cfg)
        self.rope_theta = None

    mp.setattr(hybrid.SlidingAttention, "__init__", init)


@pytest.mark.parametrize("fault", [_window_one_key_short,
                                   _window_layers_not_rotated])
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
    assert names and all(n.endswith(".kex") for n in names)
    for n in names:   # every reader is a file that loads, and finds nothing
        read = loader.load_module("layer_metrics", n).read   # in an empty run
        assert callable(read) and read({"facts": {}, "peaks": {}}) is None
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in layer)
    assert {"window_attn_time_share.kex", "full_attn_time_share.kex",
            "window_prefill_attention_roofline_share.kex",
            "window_decode_roofline_share.kex",
            "moe_gated_mlp_tm16_roofline_share.kex",
            "moe_gated_mlp_tm128_roofline_share.kex",
            "paged_decode_roofline_share.kex"} <= names
    # the other cells keep their own readers and gain none
    for cell, suffix in (("joyai_flash.ragdocs_closed", ".rag"),
                         ("olmo_hybrid.ragdocs_closed", ".hyb"),
                         ("qwen3_next.longgen_closed", ".qnx")):
        _, theirs = loader.metrics_of(cell, man)
        assert theirs and not {m["name"] for m in theirs} & names
        assert all(m["name"].endswith(suffix) for m in theirs)
    cell, config, traffic = loader.load_cell(CELL)
    # the traffic file the two other long-prompt cells run, as it is
    assert cell["traffic"] == "ragdocs_closed" == loader.load_cell(
        "joyai_flash.ragdocs_closed")[0]["traffic"]
    assert (config["serve"]["batch_size"], traffic["clients"],
            traffic["warm_seconds"]) == (32, 64, 10)
    assert traffic["prompt_buckets"] == [1536, 2048, 3072, 4096]
    assert cell["check_requests"] >= 24 and cell["chips"] == 1
    assert cell["runner"] == "serve_engine_k_exaone"
    entry = next(c for c in man["configs"] if c["name"] == "k_exaone_serve")
    assert entry["reduced"] == config["reduced"]
    assert len([w for w in man["workloads"] if w["chips"] == 4]) == 0


def test_the_kernel_counts():
    # a window layer defines min(p + 1, W) keys for the query at p
    assert kl.window_pairs(5, 128) == 15
    assert kl.window_pairs(128, 128) == 128 * 129 // 2
    assert kl.window_pairs(4096, 128) == 128 * 129 // 2 + 3968 * 128
    assert kl.window_pairs(4096, 128) == sum(min(p + 1, 128)
                                             for p in range(4096))
    assert kl.window_prefill_flops(1000, 64, 128) == 4 * 64 * 128 * 1000
    # q in and the context out of 64 heads, K and V of 8, bfloat16
    assert kl.window_prefill_bytes(4096, 64, 8, 128) == 2 * 4096 * 128 * (
        2 * 64 + 2 * 8)
    ev = {"facts": {"counters": {"admit_steps": 10, "admit_rows": 10,
                                 "admit_tokens": 25600, "decode_steps": 100,
                                 "live_slot_steps": 3000},
                    "sizes": {"num_attention_heads": 64,
                              "num_key_value_heads": 8, "head_dim": 128,
                              "sliding_window": 128},
                    "window_pairs_mean": 300000.0},
          "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    # no trace: nothing to read, and no raise
    for read in (kl.window_prefill_roofline_share,
                 kl.window_decode_roofline_share, kl.window_attn_time_share,
                 kl.full_attn_time_share, kl.moe_time_share,
                 kl.paged_decode_roofline_share,
                 kl.decode_expert_kernel_roofline_share,
                 kl.admit_expert_kernel_roofline_share,
                 kl.moe_local_pair_share):
        assert read(ev) is None
        assert read({"facts": {}, "peaks": {}}) is None
    assert kl.classify("jit(pstep)/moe/dot_general") == "moe"
    assert kl.classify("jit(pstep)/win/mul") == "win"
    assert kl.classify("jit(padmit)/attn/while/body/dot") == "attn"
    assert kl.classify("jit(padmit)/take") is None
    texts = {"step": """
  %fusion.3 = bf16[32,6144]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(pstep)/attn/dot_general" source_file="x.py"}
  ROOT %moe_gated_mlp_tm16.1 = bf16[512,6144]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(pstep)/moe/pallas_call"}
  %fusion.9 = bf16[33,128,1024]{2,1,0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(pstep)/win/scatter"}
""", "admit[4096]": """
  %fusion.3 = bf16[32,6144]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/moe/add"}
"""}
    scopes = kl.scope_map(texts)
    # fusion.3 names a different mechanism in the two programs: left out
    assert scopes == {
        "%moe_gated_mlp_tm16.1 = bf16[512,6144]{1,0}": "moe",
        "%fusion.9 = bf16[33,128,1024]{2,1,0}": "win"}
    ev = {"facts": {"op_scopes": scopes}}
    assert kl._kind(ev, "%fusion.9 = bf16[33,128,1024]{2,1,0} fusion(%p.2)"
                    ) == "win"
    assert kl._kind(ev, "%paged_decode.7 = bf16[2] custom-call()") == "attn"
    assert kl._kind(ev, "%window_decode.2 = bf16[2] custom-call()") == "win"
    assert kl._kind(ev, "%flash_fwd_window.2 = bf16[2] custom-call()") == "win"
    assert kl._kind(ev, "%flash_fwd_grouped.1 = bf16[2] custom-call()"
                    ) == "attn"
    assert kl._kind(ev, "%fusion.1 = f32[16]{0} fusion(%p.2)") is None
