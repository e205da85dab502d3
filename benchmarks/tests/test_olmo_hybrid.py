"""The cell ``olmo_hybrid.ragdocs_closed`` on the CPU rehearsal path (the
family's TINY preset, the cell's ``rehearse_limits``): a sound run comes out
``correct: true``; the fp8 control of the reference does not, nor does a
program with one fault in what the configuration added: slot state that an
admission does not reset, a conv window taken from a prompt's padding, beta
without its factor 2, the decay left out, the output gate left out."""
import importlib.util
import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.harness import hybrid_lib as hl
from benchmarks.harness import loader
from paddle_tpu.models import hybrid

CELL = "olmo_hybrid.ragdocs_closed"
GDN = hybrid.GatedDeltaNet


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
    assert deltas["gdn_prefill_tokens"] == deltas["admit_tokens"]
    assert deltas["state_bytes_steps"] > 0 and deltas["prefix_unshared"] == 0
    control = next(l for l in lines if "control_correct" in l)
    assert control == {"control_correct": False, "control_mode": "fp8"}
    assert not checks["control.mean_gap"]["ok"]
    assert not checks["control.deep_gap_share"]["ok"]


def _state_not_reset(real):
    def admit(self, x, positions, kv, rows):
        y, new = real(self, x, positions, kv, rows)
        return y, {**new, "state": new["state"].at[rows].add(
            kv["state"][rows])}
    return "admit", admit


def _window_from_padding(real):
    def admit(self, x, positions, kv, rows):
        y, new = real(self, x, positions, kv, rows)
        K = self.cfg.linear_conv_kernel
        tail = hybrid._mm(x, self.qkv.value)[:, -(K - 1):]
        return y, {**new, "conv": kv["conv"].at[rows].set(
            tail.astype(kv["conv"].dtype))}
    return "admit", admit


def _beta_without_its_factor(real):
    def gates(self, x, valid):
        g, beta = real(self, x, valid)
        return g, beta / 2
    return "_gates", gates


def _no_decay(real):
    def gates(self, x, valid):
        g, beta = real(self, x, valid)
        return jnp.zeros_like(g), beta
    return "_gates", gates


def _no_output_gate(real):
    def output(self, x, o):
        y = self.o_norm(o).reshape(*x.shape[:-1], -1).astype(x.dtype)
        return hybrid._mm(y, self.out.value)
    return "_output", output


@pytest.mark.parametrize("fault", [
    _state_not_reset, _window_from_padding, _beta_without_its_factor,
    _no_decay, _no_output_gate], ids=lambda f: f.__name__.strip("_"))
def test_a_program_with_one_fault_is_not_correct(capsys, monkeypatch, fault):
    name = fault(None)[0]
    monkeypatch.setattr(GDN, name, fault(getattr(GDN, name))[1])
    lines, checks = _run(capsys, 2 ** 31 + 11)
    assert lines[-1]["correct"] is False
    assert not checks["mean_gap"]["ok"]


def test_the_manifest_names_the_cell_and_its_readers():
    man = loader.manifest()
    e2e, layer = loader.metrics_of(CELL, man)
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in layer}
    assert names and all(n.endswith(".hyb") for n in names)
    for n in names:   # every reader is a file that loads
        assert callable(loader.load_module("layer_metrics", n).read)
    # the other closed-loop cells keep their own readers and gain none
    for cell, suffix in (("joyai_flash.ragdocs_closed", ".rag"),
                         ("gpt2_small.docs_closed", ".serve")):
        _, theirs = loader.metrics_of(cell, man)
        assert theirs and not {m["name"] for m in theirs} & names
        assert all(m["name"].endswith(suffix) for m in theirs)


def test_the_configuration_carries_the_published_widths():
    cfg = loader.load_json("configs", "olmo_hybrid_serve.json")
    want = {"hidden_size": 3840, "intermediate_size": 11008,
            "num_attention_heads": 30, "num_key_value_heads": 30,
            "linear_num_key_heads": 30, "linear_num_value_heads": 30,
            "linear_key_head_dim": 96, "linear_value_head_dim": 192,
            "linear_conv_kernel_dim": 4, "vocab_size": 100352,
            "max_position_embeddings": 65536, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 16 and len(cfg["layer_types"]) == 32
    assert cfg["published"] == {"num_hidden_layers": 32}
    fam = loader.load_module("families", "olmo_hybrid")
    kinds = fam.layer_types(cfg)
    assert kinds == (("linear_attention",) * 3 + ("full_attention",)) * 4
    # the issue's count: 88.75 M a linear mixer, 126.81 M an MLP, 4 x 3840^2
    # a full mixer, 770.7 M embedding + head
    n = {k: 1 for k in ()}
    for name, (shape, _, _) in fam.param_spec(cfg).items():
        size = 1
        for s in shape:
            size *= s
        part = name.split(".")
        key = ("vocab" if part[0] == "head" or part[1] == "embed" else
               part[3] if part[1] == "blocks" and part[2] == "0" else None)
        if key:
            n[key] = n.get(key, 0) + size
    assert n["vocab"] == 2 * 100352 * 3840
    assert n["mlp"] == 3 * 3840 * 11008
    assert round(n["mixer"] / 1e6, 2) == 88.75
    total = sum(int(jnp.prod(jnp.array(s))) for s, _, _ in
                fam.param_spec(cfg).values())
    assert round(total / 1e9, 2) == 4.10  # 3.33 B in layers + 0.77 B


def test_the_kernel_counts():
    # one chunk of one head, dk 96, dv 192: W S and (q e^b) S and the
    # state's write are 2 x 64 x 96 x 192 each, P v_new 2 x 64 x 64 x 192
    assert hl.chunk_flops(64, 1, 96, 192) == 2 * 64 * 192 * (3 * 96 + 64)
    # float32: per token three dk-wide operands, U and the scores' row in,
    # dv out; the final state once a row
    assert hl.chunk_bytes(64, 1, 1, 96, 192) == 4 * (
        64 * (3 * 96 + 192 + 64 + 192) + 96 * 192)
    assert hl.step_flops(16, 30, 96, 192) == 7 * 16 * 30 * 96 * 192
    assert hl.step_bytes(1, 1, 96, 192) == 4 * (2 * 96 * 192 + 2 * 96
                                                + 2 * 192 + 2)
    assert hl.gdn_time_share({"facts": {}}) is None
    assert hl.chunk_kernel_roofline_share({"facts": {}}) is None
    assert hl.step_kernel_roofline_share({"facts": {"counters": {
        "decode_steps": 3}}}) is None
    assert hl.classify("jit(pstep)/gdn/dot_general") == "gdn"
    assert hl.classify("jit(padmit)/attn/while/body/dot") == "attn"
    assert hl.classify("jit(padmit)/dense_mlp/dot") is None
    texts = {"step": """
  %fusion.3 = bf16[16,3840]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(pstep)/attn/dot_general" source_file="x.py"}
  ROOT %gated_delta_step.1 = f32[17,30,96,192]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(pstep)/gdn/pallas_call"}
  %fusion.9 = f32[16]{0} fusion(%p.2), kind=kLoop, metadata={op_name="jit(pstep)/dense_mlp/mul"}
""", "admit[16]": """
  %fusion.3 = bf16[16,3840]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/gdn/add"}
  %fusion.4 = bf16[2,16,3840]{2,1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(padmit)/attn/add"}
"""}
    scopes = hl.scope_map(texts)
    # fusion.3 names a different mechanism in the two programs: left out
    assert scopes == {
        "%gated_delta_step.1 = f32[17,30,96,192]{3,2,1,0}": "gdn",
        "%fusion.4 = bf16[2,16,3840]{2,1,0}": "attn"}
    ev = {"facts": {"op_scopes": scopes}}
    assert hl._kind(ev, "%fusion.4 = bf16[2,16,3840]{2,1,0} fusion(%p.1), "
                    "kind=kLoop") == "attn"
    assert hl._kind(ev, "%gated_delta_chunk.7 = f32[2] custom-call()") == "gdn"
    assert hl._kind(ev, "%fusion.9 = f32[16]{0} fusion(%p.2)") is None
