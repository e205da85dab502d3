"""The comparison that decides ``correct`` is shown to fail: the rest of a
run is driven (the CPU rehearsal path, which skips the look for a chip)
with the timed path broken underneath, and ``correct`` comes out false."""
import importlib.util
import json
import os

import pytest

from benchmarks.harness import loader


def _run(capsys, cell, seed=2 ** 31 + 11, seconds="1"):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(loader.BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    capsys.readouterr()
    rc = mod.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   seconds, "--trace", "0", "--rehearse"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert rc == 0
    return lines[-1], {l["check"]: l for l in lines if "check" in l}


def test_training_sound_then_a_step_that_returns_its_state_unchanged(
        capsys, monkeypatch):
    last, checks = _run(capsys, "bert_base.pretrain_s128")
    assert last["correct"] is True and last["device"]["platform"] == "cpu"

    from paddle_tpu.optimizer import optimizer as popt

    monkeypatch.setattr(popt.AdamW, "_rule",
                        lambda self, p, g, slots, lr, count, name: (p, slots))
    last, checks = _run(capsys, "bert_base.pretrain_s128")
    assert last["correct"] is False
    assert not checks["param_change_gap"]["ok"]
    assert not checks["grad_norm_gap"]["ok"]


@pytest.mark.parametrize("cell", ["gpt2_small.chat_open",
                                  "gpt2_small.docs_closed"])
def test_serving_sound_then_a_token_altered_where_it_is_produced(
        capsys, monkeypatch, cell):
    last, checks = _run(capsys, cell, seconds="2")
    assert last["correct"] is True

    from paddle_tpu.serving.generation import GenerationEngine

    real = GenerationEngine._finish

    def finish(self, s, now):
        s["out"][-1] = (int(s["out"][-1]) + 1) % 512
        return real(self, s, now)

    monkeypatch.setattr(GenerationEngine, "_finish", finish)
    last, checks = _run(capsys, cell, seconds="2")
    assert last["correct"] is False
    assert not checks["max_gap"]["ok"] and not checks["mean_gap"]["ok"]


def test_training_part_of_the_batch_left_out(capsys, monkeypatch):
    import numpy as np

    from paddle_tpu.static.graph import Executor

    real = Executor.run_steps

    def run_steps(self, program=None, feed=None, **kw):
        n = len(feed["input_ids"]) // 2  # second half never reaches the step
        feed = {k: np.concatenate([v[:n], v[:n]]) for k, v in feed.items()}
        return real(self, program, feed=feed, **kw)

    monkeypatch.setattr(Executor, "run_steps", run_steps)
    last, checks = _run(capsys, "bert_base.pretrain_s128")
    assert last["correct"] is False
    assert not checks["grad_norm_gap"]["ok"] or not checks["loss_gap"]["ok"]
