"""The reader of ``latent_decode``'s roofline share (PR 45's kernel, the
decode step's page walk of both latent cells; ``harness/latent_moe_lib.py``)
on a hand-made run: the bytes that a given ``kv_pages_swept_steps`` stands
for, the share they make of a traced call, and that the reader finds nothing
(and does not raise) where a counter, a fact or the kernel's events are
missing."""
import pytest

from benchmarks.harness import latent_moe_lib as lm, loader, peaks

PEAKS = peaks.peaks_for("TPU v5 lite")
MOSAIC = 'custom-call(%q, %pool), custom_call_target="tpu_custom_call"'
#: suffix -> slots; both cells: 32 heads, pages of 16 rows of 512 + 64 lanes
CELLS = {"rag": 32, "kml": 128}


def _ev(slots, trace_dir="hand-made", **without):
    """A window of 100 decode steps that swept 72 pages a slot and step;
    two latent layers' calls of 0.25 ms and 0.35 ms in the trace, beside an
    op that only READS a call's result and another kernel."""
    lm._TRACES[trace_dir] = {"ops": [
        (f"%latent_decode.4 = bf16[{slots},32,512]{{2,1,0}} {MOSAIC}",
         0.0, 250_000.0),
        (f"%latent_decode.5 = bf16[{slots},32,512]{{2,1,0}} {MOSAIC}",
         1e6, 350_000.0),
        ("%fusion.7 = bf16[8]{0} fusion(%latent_decode.4), kind=kLoop",
         2e6, 9e6),
        ("%moe_gated_mlp_tm16.1 = bf16[8]{0} custom-call(%latent_decode.5),"
         ' custom_call_target="tpu_custom_call"', 3e6, 9e6)]}
    facts = {"counters": {"decode_steps": 100,
                          "kv_pages_swept_steps": 100 * 72 * slots},
             "sizes": {"kv_lora_rank": 512, "qk_rope_head_dim": 64,
                       "num_attention_heads": 32},
             "kv_page_size": 16, "slots": slots, "trace_dir": trace_dir}
    for key in without.get("facts", ()):
        facts.pop(key)
    for key in without.get("counters", ()):
        facts["counters"].pop(key)
    for key in without.get("sizes", ()):
        facts["sizes"].pop(key)
    return {"facts": facts, "peaks": PEAKS}


def test_the_kernel_counts():
    assert lm.latent_page_lanes({"kv_lora_rank": 512,
                                 "qk_rope_head_dim": 64}) == 640
    # a swept key: its 640-lane bfloat16 row ONCE; a slot: 32 query rows of
    # 640 lanes in, 32 context rows of 512 lanes out
    assert lm.latent_decode_bytes(1000, 32, 32, 640, 512) == 2 * (
        1000 * 640 + 32 * 32 * (640 + 512))
    # per key and head a score over 640 lanes and a context over 512
    assert lm.latent_decode_flops(1000, 32, 640, 512) == 2 * 1000 * 32 * 1152


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_the_share_of_a_hand_made_run(suffix):
    slots = CELLS[suffix]
    read = loader.load_module(
        "layer_metrics", f"latent_decode_roofline_share.{suffix}").read
    keys = 72 * slots * 16           # swept pages a step x the page's rows
    nbytes = 2 * (keys * 640 + slots * 32 * 1152)
    # bound by bytes: 1280 B a key take 1.56 ns of HBM, its 73.7 kFLOP
    # 0.37 ns of the MXU; the two calls' mean is 0.3 ms
    want = 100.0 * (nbytes / 819e9) / 0.3e-3
    assert read(_ev(slots)) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("without", [
    {"counters": ["kv_pages_swept_steps"]}, {"counters": ["decode_steps"]},
    {"facts": ["kv_page_size"]}, {"facts": ["slots"]},
    {"facts": ["trace_dir"]}, {"sizes": ["kv_lora_rank"]},
    {"sizes": ["num_attention_heads"]}])
def test_nothing_to_read_is_none(without):
    assert lm.latent_decode_roofline_share(_ev(32, **without)) is None


def test_a_trace_without_the_kernel_is_none():
    ev = _ev(32, trace_dir="no-kernel")
    lm._TRACES["no-kernel"] = {"ops": [
        ("%fusion.7 = bf16[8]{0} fusion(%latent_decode.4), kind=kLoop",
         0.0, 9e6)]}
    assert lm.latent_decode_roofline_share(ev) is None
    # a parent of PR 45 gathered the slots' windows: no such kernel, no zero
    assert lm.latent_decode_roofline_share({"facts": {}, "peaks": {}}) is None
