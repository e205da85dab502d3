"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric as files of their own and one entry each in BENCHMARK.json, editing
no file that is there.  Proved by doing it in a temporary copy and running
the new cell on the CPU rehearsal path."""
import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness import loader

REPO = loader.REPO_DIR


def _add(path, obj):
    assert not os.path.exists(path), f"{path} would edit an existing file"
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


def test_a_cell_is_added_as_files(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(loader.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = dict(loader.load_json("configs", "bert_base_pretrain.json"),
               name="toy_bert")
    _add(bench / "configs" / "toy_bert.json", cfg)
    _add(bench / "traffic" / "toy_mix.json", {
        "name": "toy_mix", "generator": "fixed_batch", "task": "mlm_nsp",
        "batch": 4, "seq_len": 16, "max_predictions": 2,
        "steps_per_window": 2})
    cell = dict(loader.load_json("cells", "bert_base.pretrain_s128.json"),
                name="toy_bert.toy_mix", config="toy_bert",
                traffic="toy_mix")
    _add(bench / "cells" / "toy_bert.toy_mix.json", cell)
    _add(bench / "layer_metrics" / "toy_windows.train.py",
         'def read(ev):\n    return float(ev["facts"]["steps_per_window"])\n')
    man = loader.manifest()
    man["configs"].append({"name": "toy_bert", "source": cfg["source"],
                           "file": "benchmarks/configs/toy_bert.json",
                           "reduced": cfg["reduced"], "why": "toy"})
    man["workloads"].append({"name": cell["name"], "config": "toy_bert",
                             "traffic": "toy_mix", "chips": 1, "why": "toy"})
    for m in man["end_to_end"]:
        if m["name"] == "train_samples_s":
            m["workloads"].append(cell["name"])
    man["per_layer"].append({
        "name": "toy_windows.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "step assembly",
        "moves": "train_samples_s", "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    # the harness finds all of it by name
    e2e, layer = loader.metrics_of(cell["name"], man)
    assert {m["name"] for m in e2e} == {"train_samples_s", "setup_s"}
    assert "toy_windows.train" in {m["name"] for m in layer}
    assert "mfu.train" in {m["name"] for m in layer}   # no workloads key
    reader = loader.load_module("layer_metrics", "toy_windows.train",
                                str(bench))
    assert reader.read({"facts": {"steps_per_window": 2}}) == 2.0

    out = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", cell["name"],
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0",
         "--rehearse", "--bench-dir", str(bench)],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    assert last["metrics"] == {} and last["attempted"] > 0
    assert last["correct"] is True
    # nothing that was there was edited
    assert all(p.read_bytes() == b for p, b in before.items())


def test_cell_files_agree_with_the_manifest():
    man = loader.manifest()
    for w in man["workloads"]:
        cell, config, traffic = loader.load_cell(w["name"])
        for k in ("name", "config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        entry = next(c for c in man["configs"] if c["name"] == w["config"])
        assert entry["file"] == f"benchmarks/configs/{w['config']}.json"
        assert entry["reduced"] == config["reduced"]
        assert entry["source"] == config["source"]
        loader.load_module("families", config["family"])
        loader.load_module("generators", traffic["generator"])
        loader.load_module("runners", cell["runner"])
    for m in man["per_layer"]:
        assert callable(loader.load_module("layer_metrics", m["name"]).read)
        assert m["moves"] in {e["name"] for e in man["end_to_end"]}


def test_a_run_without_a_chip_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(loader.BENCH_DIR, "run.py"),
         "--workload", "bert_base.pretrain_s128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2
    assert '"correct"' not in out.stdout


def test_a_directory_without_the_program_fails(tmp_path):
    shutil.copytree(loader.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "bert_base.pretrain_s128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={**env, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
