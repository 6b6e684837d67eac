"""A cell of another family goes through the harness with new files alone.

The cell is built here and adds nothing to ``BENCHMARK.json``: the
classifier's configuration with a test-only model module
(``modules/models/ragged_classifier.py``: a bank of its own, an engine
keyword), reference (``modules/reference/ragged_lstm_classifier.py``:
chunks of varied length) and metric reader
(``modules/metrics/prompt_latency_p95_ms.py``: prompts alone), under the
``turns`` generator: prompts of varied length, then single-step decode
chunks chained to the previous result.
It runs end to end on the CPU (kernels in interpret mode), the chip check
skipped.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness

MODULES = Path(__file__).parent / "modules"
TRAFFIC = {"generator": "turns", "sessions": 3, "max_sessions": 3,
           "chunk_capacity": 140, "turn_rate_per_s": 1.5,
           "burst_factor": 4.0, "burst_on_s": 0.5, "burst_period_s": 2.0,
           "prompt_tokens": [8, 140], "decode_steps": [2, 4],
           "decode_chunk": 1, "think_s": 0.02, "bank_size": 64,
           "warmup_s": 0.3, "metrics_window": 321}
SEED = 2**31 + 29


def _cell():
    cfg = json.loads((harness.BENCH / "configs" / "ecg_clf_lstm_h8.json")
                     .read_text())
    cfg = dict(cfg, name="ragged_clf", model="ragged_classifier",
               reference="ragged_lstm_classifier")
    workload = {"name": "ragged_clf.turns", "config": "ragged_clf",
                "traffic": "turns", "chips": 1}
    spec = {"end_to_end": [], "per_layer": [
        {"name": "prompt_latency_p95_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "load generator",
         "moves": "latency_p95_ms"}]}
    return harness.Cell(workload["name"], workload, cfg, dict(TRAFFIC), spec)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Runs the cell once; the run, its numbers, the engine's keywords and
    the chunk length of every ``engine.step`` call."""
    with pytest.MonkeyPatch.context() as mp:
        yield _serve(mp, tmp_path_factory.mktemp("cache"))


def _serve(monkeypatch, cache):
    from repro.serve import stream

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    find = harness._module

    def test_first(kind, name):
        path = MODULES / kind / f"{name}.py"
        if not path.exists():
            return find(kind, name)
        spec = importlib.util.spec_from_file_location(
            f"bench.tests.modules.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    monkeypatch.setattr(harness, "_module", test_first)
    init, step = stream.StreamingEngine.__init__, stream.StreamingEngine.step
    options, calls = {}, []

    def spy_init(self, *args, **kw):
        options.update(kw)
        init(self, *args, **kw)

    def spy_step(self, chunks):
        calls.append({s: np.asarray(c).shape[0] for s, c in chunks.items()})
        return step(self, chunks)

    monkeypatch.setattr(stream.StreamingEngine, "__init__", spy_init)
    monkeypatch.setattr(stream.StreamingEngine, "step", spy_step)
    cell = _cell()
    run, numbers = harness.run(cell.name, SEED, 2.0, False,
                               require_chip=False, cell=cell)
    return cell, run, numbers, options, calls


def test_engine_options_reach_the_engine(served):
    _, _, _, options, _ = served
    assert options["metrics_window"] == 321


def test_varied_prompts_and_chained_steps_are_served(served):
    _, run, _, _, calls = served
    window = [n for c in calls[3:] for n in c.values()]   # after 3 warm-ups
    assert window.count(1) >= 6                     # decode steps
    prompts = {n for n in window if n > 1}
    assert len(prompts) >= 3 and max(prompts) <= 140, prompts
    assert run.in_window.sum() >= 10 and np.isfinite(run.done).all()


def test_chained_chunk_is_due_at_its_predecessors_return_plus_think(served):
    cell, run, _, _, _ = served
    _, _, t_seed = harness.derive_seeds(SEED)
    rng = np.random.default_rng(t_seed)
    harness.model(cell.cfg).chunk_bank(rng, cell.cfg, cell.traffic)
    sched = harness.generator(cell.traffic).schedule(
        cell.traffic, rng, float(TRAFFIC["warmup_s"]) + 2.0)
    chained = 0
    for i in range(TRAFFIC["sessions"]):
        mine = np.flatnonzero(run.session == i)
        due, done = run.due[mine], run.done[mine]
        for j in range(1, mine.size):
            if np.isneginf(sched.due[i][j]):             # a decode step
                assert due[j] == done[j - 1] + TRAFFIC["think_s"]
                chained += 1
            else:                                       # a turn's prompt
                assert due[j] >= done[j - 1]
    assert chained >= 6, chained


def test_check_is_finite_and_within_the_limits(served):
    cell, run, numbers, _, _ = served
    limits = harness.limits(cell)
    assert all(np.isfinite(v) for v in numbers.values()), numbers
    assert all(numbers[k] <= v for k, v in limits.items()), numbers
    assert harness.is_correct(run, numbers)
    assert run.compiles_in_window == 0


def test_a_reader_tells_prompts_from_decode_steps(served):
    from bench.stats import percentile

    _, run, _, _, _ = served
    prompt = run.steps > TRAFFIC["decode_chunk"]
    assert (run.steps[~prompt] == TRAFFIC["decode_chunk"]).all()
    for s, j, p in zip(run.session, run.index, prompt):
        assert np.isfinite(run.schedule.due[s][j]) == p
    w = run.in_window & prompt
    assert 3 <= w.sum() < run.in_window.sum()
    got = harness.read_metrics(run, "per_layer")
    assert got == {"prompt_latency_p95_ms": {
        "value": percentile(run.done[w] - run.due[w], 95) * 1e3,
        "unit": "ms"}}
