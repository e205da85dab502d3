"""Everything a run needs is found by name, so that a later PR adds a cell,
a configuration, a traffic mix, a family, a generator, a runner or a
per-layer metric by adding files (and one entry each to BENCHMARK.json) and
edits none that is there:

    cells/<cell>.json       -> config, traffic, runner, chips, limits
    configs/<config>.json   -> the sizes as run; "family" names
    families/<family>.py       the model builder and its FLOP count
    traffic/<mix>.json      -> parameters; "generator" names
    generators/<generator>.py  the one general generator that reads them
    runners/<runner>.py     -> how the system is driven and checked
    reference/<name>.py     -> the family's plain reference
    layer_metrics/<metric>.py  one reader per per-layer metric
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(*parts, bench_dir=BENCH_DIR):
    with open(os.path.join(bench_dir, *parts)) as f:
        return json.load(f)


def load_module(kind, name, bench_dir=BENCH_DIR):
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"nothing named {name!r} under {kind}/: "
                                f"{path} does not exist")
    mod_name = "benchmarks_%s_%s" % (kind, "".join(
        c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(repo_dir=REPO_DIR):
    with open(os.path.join(repo_dir, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name, bench_dir=BENCH_DIR):
    cell = load_json("cells", name + ".json", bench_dir=bench_dir)
    config = load_json("configs", cell["config"] + ".json",
                       bench_dir=bench_dir)
    traffic = load_json("traffic", cell["traffic"] + ".json",
                        bench_dir=bench_dir)
    return cell, config, traffic


def metrics_of(cell_name, man):
    """The end-to-end and per-layer metric entries of BENCHMARK.json that
    the cell reports: an entry with a ``workloads`` key lists its cells; a
    per-layer entry without one goes with every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if (cell_name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer
