"""The reader of how often the serving loop ran a decode step ahead
(``harness/ahead_lib.py``): its arithmetic, that it finds nothing (and does
not raise) on a program from before the counter, and that each serving cell
has its entry and its file."""
import pytest

from benchmarks.harness import ahead_lib, loader

CELLS = {"serve": "gpt2_small.docs_closed", "chat": "gpt2_small.chat_open",
         "rag": "joyai_flash.ragdocs_closed",
         "hyb": "olmo_hybrid.ragdocs_closed",
         "qnx": "qwen3_next.longgen_closed"}


def _ev(counters):
    return {"facts": {"counters": counters}, "seconds": 1e9, "trace": None}


def test_share_of_steps_and_none_without_the_counter():
    read = ahead_lib.decode_ahead_share
    assert read(_ev({"decode_steps": 200, "decode_steps_ahead": 180})) \
        == pytest.approx(90.0)
    assert read(_ev({"decode_steps": 200, "decode_steps_ahead": 0})) == 0.0
    # the parent's program has no such counter; a window without a step
    assert read(_ev({"decode_steps": 200})) is None
    assert read(_ev({"decode_steps": 0, "decode_steps_ahead": 0})) is None
    assert read({"facts": {}}) is None


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_each_serving_cell_reports_it(suffix):
    man = loader.manifest()
    name = "decode_ahead_share." + suffix
    (entry,) = [m for m in man["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELLS[suffix]]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "serving scheduler"
    assert entry in loader.metrics_of(CELLS[suffix], man)[1]
    assert loader.load_module("layer_metrics", name).read(
        _ev({"decode_steps": 4, "decode_steps_ahead": 3})) == 75.0
