"""The reducer against a small trace recorded on a TPU v5 lite (PR 23's
first chip call: 12 iterations of a jitted matmul + the repo's
layernorm_residual Pallas kernel, a second jitted matmul, a D2H read and a
3 ms sleep, under the benchmark's own host spans)."""
import os

import pytest

from benchmarks.harness import layer_lib, trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "probe_v5e.xplane.pb")
SPANS = ("window_dispatch", "loss_readback", "schedule_wait")


@pytest.fixture(scope="module")
def reduced():
    trace = tr.load(DATA, SPANS + ("window",))
    return trace, tr.reduce(trace, step_module="jit_step",
                            gap_span_names=SPANS)


def test_planes_and_lines(reduced):
    trace, _ = reduced
    assert list(trace["devices"]) == [0]
    assert len(trace["devices"][0]["ops"]) == 204
    assert len(trace["devices"][0]["modules"]) == 48
    assert sum(1 for s in trace["host_spans"] if s[0] == "window") == 1


def test_idle_share(reduced):
    _, red = reduced
    assert red["window_ns"] == pytest.approx(190.39e6, rel=1e-3)
    assert red["busy_ns"] == pytest.approx(1.7807e6, rel=1e-3)
    ev = {"trace": red}
    assert layer_lib.device_idle_share(ev) == pytest.approx(99.065, abs=0.01)


def test_a_mosaic_kernel_is_told_by_its_custom_call_target(reduced):
    trace, red = reduced
    mosaic = [d for n, _, d in trace["devices"][0]["ops"]
              if tr.MOSAIC_MARK in n]
    # 12 executions of the kernel at ~11.34 us each
    assert sum(mosaic) == pytest.approx(12 * 11342, rel=0.01)
    assert ["step.1:tpu_custom_call", pytest.approx(136.1e-6, rel=0.01)] in \
        red["top_ops"]
    # XLA's own small custom-calls are not Mosaic kernels
    assert sum(mosaic) < sum(v for k, v in red["top_ops"]
                             if "custom" in k) * 1e9


def test_module_durations_and_gaps(reduced):
    _, red = reduced
    step = red["step"]
    assert step["module"].startswith("jit_step(")
    # 12 ran; the first is the line's first event (possibly cut) and is left out
    assert step["executions"] == 11
    assert all(70e3 < d < 85e3 for d in step["durations_ns"])  # ~76 us
    ev = {"trace": red, "facts": {"flops_per_step": 2 * 4096 * 1024 * 1024},
          "chips": 1, "peaks": {"bf16_flops": 197e12}}
    assert layer_lib.step_device_ms(ev) == pytest.approx(0.0762, abs=0.002)
    assert layer_lib.host_gap_ms(ev) > 1.0     # a read-back and a sleep
    assert 0 < layer_lib.mfu(ev) < 100


def test_gap_attribution(reduced):
    _, red = reduced
    causes = dict(red["idle_by_cause"])
    assert causes["loss_readback"] > causes["schedule_wait"] > 0.03
    assert red["longest_gaps"][0][0] == "loss_readback"
    assert sum(causes.values()) == pytest.approx(
        (red["window_ns"] - red["busy_ns"]) / 1e9, rel=1e-6)


def test_a_serving_gap_is_named_by_the_loop_s_phase_not_the_generator_s_nap():
    # the device ran at 0-1, 9-10 and 20-21 ms; the generator napped all the
    # while, the serving loop was in a 3 ms admission call, then in 2 ms of
    # harvest
    from benchmarks.harness.context import HOST_SPANS

    ms = 1e6
    trace = {"devices": {0: {"modules": [], "ops": [
        ("%a = f32[] add()", 0.0, ms), ("%b = f32[] add()", 9 * ms, ms),
        ("%c = f32[] add()", 20 * ms, ms)]}},
        "host_spans": [("schedule_wait", 0.0, 30 * ms),
                       ("serve/admit.device", 2 * ms, 3 * ms),
                       ("serve/harvest", 5 * ms, 2 * ms)]}
    plain = tr.reduce(trace)  # every span read competes: the nap wins
    assert [g[0] for g in plain["longest_gaps"]] == ["schedule_wait"] * 2
    red = tr.reduce(trace, gap_span_names=HOST_SPANS)
    # the 8 ms gap goes to the phase that covers most of it; no phase
    # touches the 10 ms gap
    assert red["longest_gaps"] == [["unattributed", pytest.approx(0.010)],
                                   ["serve/admit.device",
                                    pytest.approx(0.008)]]
    assert dict(red["idle_by_cause"]) == {
        "unattributed": pytest.approx(0.010),
        "serve/admit.device": pytest.approx(0.008)}


def test_interval_arithmetic():
    assert tr.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert tr.gaps([(5, 5), (20, 5)], 0, 30) == [(0, 5), (10, 10), (25, 5)]
    assert tr.short_op_name(
        '%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kLoop'
    ) == "fusion.3:fusion"


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {0: {"modules": [], "ops": []}},
                   "host_spans": []})

