"""The cell ``joyai_flash.ragdocs_closed`` on the CPU rehearsal path (the
family's TINY preset, the cell's ``rehearse_limits``): a sound run comes
out ``correct: true``; the fp8 control of the reference and a program that
leaves the shared expert out come out ``correct: false``."""
import importlib.util
import json
import os

import pytest

from benchmarks.harness import loader

CELL = "joyai_flash.ragdocs_closed"


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
    assert checks["moe_dropped_tokens"]["value"] == 0
    control = next(l for l in lines if "control_correct" in l)
    assert control == {"control_correct": False, "control_mode": "fp8"}
    assert not checks["control.mean_gap"]["ok"]
    assert not checks["control.deep_gap_share"]["ok"]


def test_a_program_without_the_shared_expert_is_not_correct(
        capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.moe.layer import DroplessMoE

    real = DroplessMoE.forward

    def forward(self, x):
        xf = jnp.asarray(x).reshape(-1, x.shape[-1])
        g = xf @ self.shared_gate.value
        shared = (jax.nn.silu(g) * (xf @ self.shared_up.value)
                  ) @ self.shared_down.value
        return real(self, x) - shared.reshape(x.shape).astype(x.dtype)

    monkeypatch.setattr(DroplessMoE, "forward", forward)
    lines, checks = _run(capsys, 2 ** 31 + 11)
    assert lines[-1]["correct"] is False
    assert not checks["mean_gap"]["ok"]


def test_the_manifest_names_the_cell_and_its_readers():
    man = loader.manifest()
    e2e, layer = loader.metrics_of(CELL, man)
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in layer}
    assert names and all(n.endswith(".rag") for n in names)
    assert {"moe_gated_mlp_tm16_roofline_share.rag",
            "moe_gated_mlp_tm128_roofline_share.rag",
            "latent_prefill_attention_roofline_share.rag",
            "latent_decode_roofline_share.rag", "mla_time_share.rag",
            "moe_time_share.rag", "experts_touched_per_step.rag"} <= names
    # the accepted closed-loop readers stay with the cell they were for
    _, docs = loader.metrics_of("gpt2_small.docs_closed", man)
    assert docs and not {m["name"] for m in docs} & names
    assert all(m["name"].endswith(".serve") for m in docs)


def test_the_kernel_counts():
    from benchmarks.harness import latent_moe_lib as lm

    # one row through one expert of 2048 x 768: 3 matmuls of 2 x d x f
    assert lm.gated_mlp_flops(1, 2048, 768) == 6 * 2048 * 768
    # bf16: the expert's three matrices once, the row in and out
    assert lm.gated_mlp_bytes(1, 1, 2048, 768) == 2 * (3 * 2048 * 768
                                                       + 2 * 2048)
    # 3 visible pairs (a 2-token prompt), 32 heads of 192 | 128
    assert lm.prompt_attention_flops(3, 32, 192, 128) == 2 * 32 * 320 * 3
    ev = {"facts": {"counters": {"moe_experts_touched": 640,
                                 "moe_layer_steps": 4},
                    "expert_routed": [3, 1, 0, 0]}}
    assert lm.experts_touched_per_step(ev) == 160.0
    assert lm.expert_load_max_over_mean(ev) == 3.0
    assert lm.moe_time_share({"facts": {}}) is None
    assert lm.classify("jit(pstep)/moe/dot_general") == "moe"
    assert lm.classify("jit(padmit)/mla/while/body/dot") == "mla"
    assert lm.classify("jit(padmit)/dense_mlp/dot") is None
    texts = {"step": """
  %fusion.3 = bf16[32,2048]{1,0:T(8,128)(2,1)} fusion(%p.1), kind=kLoop, metadata={op_name="jit(pstep)/mla/dot_general" source_file="x.py"}
  ROOT %moe_gated_mlp_tm16.1 = bf16[4352,2048]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(pstep)/moe/pallas_call"}
  %fusion.9 = f32[32]{0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(pstep)/dense_mlp/mul"}
""", "admit[16]": """
  %fusion.3 = bf16[32,2048]{1,0:T(8,128)(2,1)} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/moe/add"}
  %fusion.4 = bf16[2,16,2048]{2,1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/mla/add"}
"""}
    scopes = lm.scope_map(texts)
    # fusion.3 names a different mechanism in the two programs: left out
    assert scopes == {"%moe_gated_mlp_tm16.1 = bf16[4352,2048]{1,0}": "moe",
                      "%fusion.4 = bf16[2,16,2048]{2,1,0}": "mla"}
    ev = {"facts": {"op_scopes": scopes}}
    assert lm._kind(ev, "%fusion.4 = bf16[2,16,2048]{2,1,0} fusion(bf16[2] "
                    "%p.1), kind=kLoop") == "mla"
    assert lm._kind(ev, "%fusion.9 = f32[32]{0} fusion(%p.2)") is None
