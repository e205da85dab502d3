"""The reader of how much of the admission width's attention square the
``paged_decode`` kernel walks (``harness/walk_lib.py``): its arithmetic,
that it finds nothing (and does not raise) on a program from before the
counters, and that each of the two GPT-2 cells has its entry and its file."""
import pytest

from benchmarks.harness import loader, walk_lib

CELLS = {"serve": "gpt2_small.docs_closed", "chat": "gpt2_small.chat_open"}
MOVES = {"serve": "serve_tok_s", "chat": "req_latency_p95_ms"}


def _ev(counters):
    return {"facts": {"counters": counters}, "seconds": 1e9, "trace": None}


def test_share_of_the_square_and_none_without_the_counters():
    read = walk_lib.admit_attn_walked_share
    # a 704-token and a 512-token row in a 768 bucket: 21 + 10 of 36 + 24
    assert read(_ev({"admit_attn_blocks_walked": 31,
                     "admit_attn_blocks_square": 60})) \
        == pytest.approx(100 * 31 / 60)
    assert read(_ev({"admit_attn_blocks_walked": 5,
                     "admit_attn_blocks_square": 5})) == 100.0
    # the parent's program has no such counters; a window with no admission
    assert read(_ev({"admit_rows": 200})) is None
    assert read(_ev({"admit_attn_blocks_walked": 0,
                     "admit_attn_blocks_square": 0})) is None
    assert read({"facts": {}}) is None


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_each_gpt2_cell_reports_it(suffix):
    man = loader.manifest()
    name = "admit_attn_walked_share." + suffix
    (entry,) = [m for m in man["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELLS[suffix]]
    assert entry["moves"] == MOVES[suffix]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "kernels" and entry["better"] == "lower"
    assert entry in loader.metrics_of(CELLS[suffix], man)[1]
    assert loader.load_module("layer_metrics", name).read(
        _ev({"admit_attn_blocks_walked": 2,
             "admit_attn_blocks_square": 8})) == 25.0
