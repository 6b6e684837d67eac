"""The program's tick spans and counters as the benchmark reads them."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import engine_trace, harness, trace_reduce

DATA = Path(__file__).parent / "data"


def _events():
    # Two ticks (ns).  Harness: wait 0-10, step 10-60, block 60-70, wait
    # 70-100, step 100-150, block 150-160.  The program's engine.step spans
    # sit inside the harness's, a layer span per dispatch.  Device: tick 1
    # kernels 22-26, 33-37 (a copy 36-38 beside it), 44-47; tick 2 kernels
    # 112-116, 121-125, 131-134; a fetch copy 61-63.
    spans = [["arrive_wait", 0, 10], ["step", 10, 60], ["block", 60, 70],
             ["arrive_wait", 70, 100], ["step", 100, 150],
             ["block", 150, 160],
             ["engine.step", 12, 58], ["engine.launch", 18, 46],
             ["engine.step", 102, 148], ["engine.launch", 108, 136]]
    for t0 in (20, 110):
        spans += [[f"rnn.layer{i}", t0 + 10 * i, t0 + 10 * i + 5]
                  for i in range(3)]
    ops = [["k", 22, 26, True], ["k", 33, 37, True], ["copy", 36, 38, False],
           ["k", 44, 47, True], ["copy", 61, 63, False],
           ["k", 112, 116, True], ["k", 121, 125, True],
           ["k", 131, 134, True]]
    return {"spans": spans, "devices": {"/device:TPU:0": ops}}


def test_launch_gap_hand_reduced():
    # Tick 1: 22-47 spans 25, busy 4 + 5 + 3 -> 13 idle; tick 2: 112-134
    # spans 22, busy 4 + 4 + 3 -> 11 idle.  Mean 12 ns.
    assert engine_trace.launch_gap_ms_per_tick(_events()) == \
        pytest.approx(12e-6)


def test_host_bound_idle_hand_reduced():
    # engine.step 12-58 holds 12 busy of 46, 102-148 holds 11 of 46:
    # 69 idle over the 160 window.
    assert engine_trace.host_bound_idle_pct(_events()) == \
        pytest.approx(69 / 160 * 100)


def test_no_engine_span_reads_none():
    ev = _events()
    ev["spans"] = [s for s in ev["spans"]
                   if not s[0].startswith(("engine.", "rnn."))]
    assert engine_trace.launch_gap_ms_per_tick(ev) is None
    assert engine_trace.host_bound_idle_pct(ev) is None
    assert engine_trace.launch_gap_ms_per_tick(None) is None
    assert engine_trace.host_bound_idle_pct(None) is None
    assert engine_trace.events(SimpleNamespace(trace=None)) is None


def test_recorded_chip_trace():
    # Three seconds of clf_icu_pod16 traced on one TPU v5e with the
    # program's spans: 54 ticks, three layer dispatches each; spans and
    # operations cut to the harness's window.
    ev = trace_reduce.load(DATA / "icu_pod16_engine_trace_events.json.gz")
    names = [n for n, *_ in ev["spans"]]
    assert names.count("engine.step") == 54
    assert sum(n.startswith("rnn.layer") for n in names) == 3 * 54
    gap = engine_trace.launch_gap_ms_per_tick(ev)
    assert gap == pytest.approx(0.13309342592592593)
    idle = engine_trace.host_bound_idle_pct(ev)
    assert idle == pytest.approx(47.01482036722878)
    bare = {"spans": [s for s in ev["spans"] if s[0] in harness.SPANS],
            "devices": ev["devices"]}
    r = trace_reduce.reduce(bare, chips=1)
    assert r["ticks"] == 54 and r["kernel_launches"] == 3 * 54
    # The host's share of the idle time is part of all of it.
    assert 0 < idle < (1 - r["busy_s"] / r["window_s"]) * 100
    # The same trace without the program's spans reads nothing.
    assert engine_trace.launch_gap_ms_per_tick(bare) is None
    assert engine_trace.host_bound_idle_pct(bare) is None


def test_phase_ms_reads_tick_metrics():
    rec = [SimpleNamespace(phase_s={"engine.stage": 1e-3,
                                    "engine.writeback": 3e-3}),
           SimpleNamespace(phase_s={"engine.stage": 3e-3,
                                    "engine.writeback": 1e-3})]
    run = SimpleNamespace(tick_metrics=rec)
    assert engine_trace.phase_ms(run, "engine.stage") == pytest.approx(2.0)
    assert engine_trace.phase_ms(run, "engine.stage", "engine.writeback") \
        == pytest.approx(4.0)
    # A program whose records carry no phases, or a run with no tick.
    bare = SimpleNamespace(tick_metrics=[SimpleNamespace(duration_s=0.1)])
    assert engine_trace.phase_ms(bare, "engine.stage") is None
    assert engine_trace.phase_ms(SimpleNamespace(tick_metrics=[]),
                                 "engine.stage") is None


def test_tick_metric_readers_and_a_program_without_them():
    phases = {"engine.stage": 1e-3, "engine.carry_gather": 2e-3,
              "engine.writeback": 3e-3, "engine.summarize": 4e-3,
              "engine.launch": 5e-3}
    run = SimpleNamespace(tick_metrics=[
        SimpleNamespace(phase_s=phases, gc_s=6e-3)] * 2, trace=None)
    want = {"stage_ms_per_tick": 1.0, "carry_ms_per_tick": 5.0,
            "summary_ms_per_tick": 4.0, "launch_ms_per_tick": 5.0,
            "gc_ms_per_tick": 6.0}
    bare = SimpleNamespace(tick_metrics=[SimpleNamespace(duration_s=0.1)],
                           trace=None)
    for name, value in want.items():
        reader = harness._module("metrics", name)
        assert reader.read(run) == pytest.approx(value)
        assert reader.read(bare) is None
    for name in ("launch_gap_ms_per_tick", "host_bound_idle_pct"):
        assert harness._module("metrics", name).read(bare) is None
