"""What has to hold of ``BENCHMARK.json``'s per-layer entries however many a
PR appends and in whatever order: every entry has a reader file that
imports, every reader file has an entry, every entry names cells that exist
and report the end-to-end metric it moves, and the list fits the contract's
128.  (Tests of an ORDER or a COUNT of entries broke with every appending
PR; PR 49 replaced five of them by this.)"""
import os
import re

import pytest

from benchmarks.harness import loader

MAN = loader.manifest()
ENTRIES = {m["name"]: m for m in MAN["per_layer"]}
CELLS = {w["name"] for w in MAN["workloads"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
FIELDS = {"name", "unit", "better", "source", "layer", "moves"}


def test_the_list_fits_the_contract_and_names_nothing_twice():
    assert 1 <= len(MAN["per_layer"]) <= 128
    assert len(ENTRIES) == len(MAN["per_layer"])
    assert not set(ENTRIES) & {m["name"] for m in MAN["end_to_end"]}


def test_every_reader_file_has_an_entry():
    files = {f[:-3] for f in os.listdir(
        os.path.join(loader.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert files == set(ENTRIES)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_has_its_reader_its_cells_and_its_fields(name):
    m = ENTRIES[name]
    assert NAME.match(name) and FIELDS <= set(m) <= FIELDS | {"workloads"}
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    reader = loader.load_module("layer_metrics", name)
    assert callable(reader.read) and reader.__doc__
    # a run that left nothing to read: the metric is left out, not raised
    assert reader.read({"facts": {}, "peaks": {}, "trace": None}) is None
    moved = next(e for e in MAN["end_to_end"] if e["name"] == m["moves"])
    cells = m.get("workloads") or [
        c for c in CELLS if "workloads" not in moved
        or c in moved["workloads"]]
    assert cells and set(cells) <= CELLS
    for cell in cells:   # the cell reports what the metric moves
        e2e, layer = loader.metrics_of(cell, MAN)
        assert m["moves"] in {e["name"] for e in e2e} and m in layer
        assert os.path.exists(os.path.join(loader.BENCH_DIR, "cells",
                                           cell + ".json"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_reports_setup_another_end_to_end_metric_and_a_layer(cell):
    e2e, layer = loader.metrics_of(cell, MAN)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer
    # one suffix a cell: a reader never goes to a cell it was not written for
    assert len({m["name"].rsplit(".", 1)[1] for m in layer}) == 1
