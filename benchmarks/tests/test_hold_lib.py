"""The reader of how many admitted rows waited for a partner
(``harness/hold_lib.py``): its arithmetic, that it finds nothing (and does
not raise) on a program from before the counter, and that each of the three
cells whose buckets keep two rows has its entry and its file."""
import pytest

from benchmarks.harness import hold_lib, loader

CELLS = {"serve": "gpt2_small.docs_closed", "chat": "gpt2_small.chat_open",
         "qnx": "qwen3_next.longgen_closed"}
MOVES = {"serve": "serve_tok_s", "chat": "req_latency_p95_ms",
         "qnx": "serve_tok_s"}


def _ev(counters):
    return {"facts": {"counters": counters}, "seconds": 1e9, "trace": None}


def test_share_of_rows_and_none_without_the_counter():
    read = hold_lib.admit_held_row_share
    assert read(_ev({"admit_rows": 200, "admit_rows_held": 90})) \
        == pytest.approx(45.0)
    assert read(_ev({"admit_rows": 68, "admit_rows_held": 0})) == 0.0
    # the parent's program has no such counter; a window without an admission
    assert read(_ev({"admit_rows": 200})) is None
    assert read(_ev({"admit_rows": 0, "admit_rows_held": 0})) is None
    assert read({"facts": {}}) is None


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_each_two_row_cell_reports_it(suffix):
    man = loader.manifest()
    name = "admit_held_row_share." + suffix
    (entry,) = [m for m in man["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELLS[suffix]]
    assert entry["moves"] == MOVES[suffix]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "serving scheduler"
    assert entry in loader.metrics_of(CELLS[suffix], man)[1]
    assert loader.load_module("layer_metrics", name).read(
        _ev({"admit_rows": 8, "admit_rows_held": 2})) == 25.0

