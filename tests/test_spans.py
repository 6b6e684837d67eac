"""Spans and counters of the engine tick (``repro.serve.spans``).

Each ``StreamingEngine.step`` is one ``engine.step`` span whose phases are
child spans; the tick's ``TickMetrics`` carries their host times
(``phase_s``), the backend compiles (``compiles``, through
``jax.monitoring``) and the Python GC pauses (``gc_s``) that fell inside it.
"""

import gc
import json
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import classifier as clf, mcd
from repro.serve import (FleetEngine, JsonlSink, SimulatedLoadSink,
                         StreamingEngine, TenantSpec, TickMetrics, prewarm)
from repro.serve import spans


def _engine(s=3, hidden=8, classes=4, **kw):
    cfg = clf.ClassifierConfig(
        hidden=hidden, num_layers=2, num_classes=classes,
        mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s, seed=3))
    return StreamingEngine(clf.init(jax.random.key(0), cfg), cfg, **kw)


def test_phase_s_holds_the_span_names_and_fits_in_duration():
    eng = _engine(max_sessions=2, chunk_capacity=4)
    eng.open_session("a")
    eng.open_session("b")
    for n in (4, 2):
        eng.step({"a": jnp.ones((n, 1)), "b": jnp.ones((3, 1))})
    for m in eng.metrics:
        assert tuple(m.phase_s) == spans.PHASES
        assert all(v >= 0.0 for v in m.phase_s.values())
        assert sum(m.phase_s.values()) <= m.duration_s
        assert m.phase_s["engine.launch"] > 0.0
        assert m.gc_s >= 0.0


def test_span_names_stay_clear_of_the_benchmark_spans():
    # bench/trace_reduce.py windows on its own four spans.
    harness = {"arrive_wait", "submit", "step", "block"}
    assert not harness & {"engine.step", "engine.gc", *spans.PHASES}
    assert all(p.startswith("engine.") for p in spans.PHASES)


def test_compiles_count_eager_summary_ops_after_prewarm():
    # Shapes of its own (S=7, 7 classes, hidden 7): nothing else in the
    # process has compiled this engine's graphs or its summary ops.
    eng = _engine(s=7, hidden=7, classes=7, max_sessions=2,
                  chunk_capacity=4)
    prewarm(eng)                        # the stack graph is warm
    eng.open_session("a")
    eng.step({"a": jnp.ones((4, 1))})
    first = eng.last_metrics.compiles
    assert first >= 1                   # the eager summary ops compiled
    eng.step({"a": jnp.ones((2, 1))})
    assert eng.last_metrics.compiles == 0   # same tick size: all warm
    eng.open_session("b")
    eng.step({"a": jnp.ones((4, 1)), "b": jnp.ones((4, 1))})
    assert eng.last_metrics.compiles >= 1   # a new tick size compiles


def test_forced_collection_inside_a_tick_shows_in_gc_s(monkeypatch):
    eng = _engine(max_sessions=1, chunk_capacity=4)
    eng.open_session("a")
    eng.step({"a": jnp.ones((4, 1))})
    escalate = eng._escalate
    forced = []

    def collect_then_escalate(*args):
        garbage = [[i] for i in range(200_000)]
        garbage.append(garbage)         # a cycle only the collector frees
        del garbage
        t0 = time.perf_counter()
        gc.collect()
        forced.append(time.perf_counter() - t0)
        return escalate(*args)

    monkeypatch.setattr(eng, "_escalate", collect_then_escalate)
    eng.step({"a": jnp.ones((4, 1))})
    m = eng.last_metrics
    assert m.gc_s >= 0.5 * forced[0] > 0.0


def test_tick_record_sums_its_phases():
    with spans.tick(7) as rec:
        for _ in range(2):
            with rec.phase("engine.stage"):
                time.sleep(1e-3)
    assert rec.number == 7 and rec.phase_s["engine.stage"] >= 2e-3
    assert rec.duration_s >= rec.phase_s["engine.stage"]
    assert rec.compiles == 0


def test_jsonl_sink_round_trips_the_new_fields(tmp_path):
    eng = _engine(max_sessions=1, chunk_capacity=4,
                  metrics_sink=JsonlSink(str(tmp_path / "ticks.jsonl")))
    eng.open_session("a")
    eng.step({"a": jnp.ones((4, 1))})
    eng.metrics_sink.close()
    (line,) = (tmp_path / "ticks.jsonl").read_text().splitlines()
    rec = json.loads(line)
    assert set(rec["phase_s"]) == set(spans.PHASES)
    assert TickMetrics(**rec) == eng.last_metrics


def test_records_without_the_new_fields_still_build():
    m = TickMetrics(tick=0, capacity=4, n_chunks=1, live_rows=2,
                    batch_rows=2, queue_depth=0, live_steps=4,
                    live_chain_steps=8, padded_steps=8, pad_waste=0.0,
                    duration_s=0.5, tokens_per_sec=16.0)
    assert m.phase_s == {} and m.gc_s == 0.0 and m.compiles == 0


def test_simulated_load_sink_keeps_the_phases():
    sink = SimulatedLoadSink(per_chain_step_s=1e-5, overhead_s=2e-4)
    eng = _engine(max_sessions=1, chunk_capacity=4, metrics_sink=sink)
    eng.open_session("a")
    eng.step({"a": jnp.ones((4, 1))})
    m = eng.last_metrics
    assert m.duration_s == pytest.approx(2e-4 + 1e-5 * 3 * 4)
    assert tuple(m.phase_s) == spans.PHASES


def test_fleet_records_carry_the_group_phases_and_quiet_ones_build():
    cfg = clf.ClassifierConfig(
        hidden=8, num_layers=2, num_classes=4,
        mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=3, seed=3))
    params = clf.init(jax.random.key(0), cfg)
    fleet = FleetEngine([
        TenantSpec(name="ward", cfg=cfg, params=params, max_sessions=2,
                   backend="reference"),
        TenantSpec(name="icu", cfg=cfg, params=params, max_sessions=2,
                   backend="reference")], admit_per_tick=1)
    fleet.admit("ward", "p1")
    fleet.admit("icu", "p1")
    fleet.step({})                      # one tenant waits: a quiet record
    (quiet,) = [m for m in fleet.metrics if m.n_chunks == 0]
    assert quiet.phase_s == {} and quiet.gc_s == 0.0
    (served,) = [t for t in ("ward", "icu")
                 if fleet.queue.depth_of(t) == 0]
    fleet.step({served: {"p1": jnp.ones((4, 1))}})
    rec = [m for m in fleet.metrics if m.tenant == served][-1]
    assert tuple(rec.phase_s) == spans.PHASES and rec.n_chunks == 1
