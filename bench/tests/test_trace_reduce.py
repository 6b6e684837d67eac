"""The reduction from trace events to device numbers."""

from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data"


def _events():
    # Host spans: wait 0-10, step 10-30, block 30-40 (ns).  Device ops:
    # a kernel 12-20, a copy 18-22 (overlapping), a kernel 25-28, and an
    # op outside the window.
    return {
        "spans": [["arrive_wait", 0, 10], ["step", 10, 30],
                  ["block", 30, 40]],
        "devices": {"/device:TPU:0": [
            ["lstm", 12, 20, True], ["copy", 18, 22, False],
            ["lstm", 25, 28, True], ["late", 50, 60, False]]},
    }


def test_hand_reduced_window():
    r = trace_reduce.reduce(_events(), chips=1)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(13e-9)       # 12-22 and 25-28
    assert r["kernel_s"] == pytest.approx(11e-9)
    assert r["kernel_launches"] == 2
    assert r["ticks"] == 1
    # Gaps 0-12 (wait 10 of it), 22-25 (step), 28-40 (block 10, step 2).
    assert r["idle_gaps"] == [["arrive_wait", pytest.approx(12e-9)],
                              ["block", pytest.approx(12e-9)],
                              ["step", pytest.approx(3e-9)]]
    assert r["device_ops"][0] == ["lstm", pytest.approx(11e-9)]


def test_nothing_traced_reads_nothing():
    assert trace_reduce.reduce({"spans": [], "devices": {}}, chips=1) is None
    ev = _events()
    ev["devices"] = {"/device:TPU:0": []}
    assert trace_reduce.reduce(ev, chips=1) is None


def test_kernel_marking():
    assert trace_reduce.is_kernel("fusion.3", {"hlo_op": "tpu_custom_call"})
    assert not trace_reduce.is_kernel("fusion.3", {"hlo_op": "fusion"})


def test_recorded_chip_trace():
    # Three seconds of clf_icu_pod16 traced on one TPU v5e: 59 ticks of
    # three kernel launches each (one per layer).
    r = trace_reduce.reduce(
        trace_reduce.load(DATA / "icu_pod16_trace_events.json.gz"), chips=1)
    assert r["ticks"] == 59
    assert r["kernel_launches"] == 3 * r["ticks"]
    assert r["window_s"] == pytest.approx(2.95578702)
    assert r["busy_s"] == pytest.approx(0.070390644)
    assert r["kernel_s"] == pytest.approx(0.057229008)
    assert r["device_ops"][0] == ["mcd_lstm_seq.1",
                                  pytest.approx(0.057229008)]
    assert all(name == "arrive_wait" for name, _ in r["idle_gaps"])
    assert 0 < r["busy_s"] < r["window_s"]
