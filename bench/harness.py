"""One run of one cell: build, warm up, serve open-loop traffic, check.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file of its ``config``, the traffic file
``bench/traffic/<traffic>.json``, the traffic's generator
(``bench/generators/<generator>.py``, see ``bench/loadgen.py``), the model
and reference modules that the configuration file names
(``bench/models/<model>.py``, ``bench/reference/<reference>.py``) and one
reader per metric (``bench/metrics/<metric>.py``).  The model module draws
the bank the chunks are cut from and may add engine keywords.  A new cell,
configuration, family, traffic generator or metric is new files and a new
entry, never an edit here.

A run, in order:

1. set-up: weights from the seed in one jitted call on the device, the
   engine (``repro.serve.StreamingEngine`` on ``backend="pallas_seq"`` at
   the traffic's fixed launch shape), every session admitted, the launch
   prewarmed (``repro.serve.scheduler.prewarm``), then one tick of every
   size from ``sessions`` down to 1, so that each tick size the traffic can
   make has compiled its eager summary ops before the window;
2. the traffic's own warm-up (``warmup_s``), then the window of
   ``--seconds``: each session's chunks fall due on its schedule, at fixed
   times or chained to the session's previous result; whenever chunks are
   due the loop hands the oldest due chunk of every such session to one
   ``engine.step`` and fetches the tick's summaries to the host
   (``jax.device_get``).  A chunk's latency runs from its due time to that
   fetch.  Chunks due in the window and still queued when it closes are
   served and timed after it; a chained chunk that would fall due after
   the window closes is not sent, nor any later chunk of its session;
3. the program's state is read (peak memory) and freed, and the reference
   replays every session's chunks, in order, from the same seed, once with
   the carry and once without it; the comparison decides ``correct``.

The program's matmuls run at the configuration's ``dtype`` throughout.

With ``trace`` the profiler runs from the traffic's start to the end of
the drain (started before the traffic: starting it stalls the host), and
the harness's calls from the window's start on are wrapped in
``arrive_wait``, ``submit``, ``step`` and ``block`` spans, which bound the
part of the trace the reduction reads.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import loadgen, roofline, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / "out" / "trace"
SPANS = ("arrive_wait", "submit", "step", "block")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    cfg: dict
    traffic: dict
    spec: dict


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    return Cell(name, wl, cfg, traffic, spec)


def _module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict):
    return _module("reference", cfg["reference"])


def model(cfg: dict):
    return _module("models", cfg["model"])


def generator(traffic: dict):
    return _module("generators", traffic.get("generator", "periodic"))


def derive_seeds(seed: int) -> tuple[int, int, int]:
    """Weight seed, mask seed and traffic seed, all from ``--seed``."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    w, m, t = (int(v) for v in ss.generate_state(3))
    return w & 0x7FFFFFFF, m & 0x7FFFFFFF, t


def check_chips(chips: int):
    """The devices to run on; raises :class:`NoChip` without enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def build_engine(cfg: dict, traffic: dict, w_seed: int, mc_seed: int,
                 chips: int):
    """The program's engine for this configuration, weights on the device."""
    import jax

    from repro.core import mcd
    from repro.serve import StreamingEngine

    mc = mcd.MCDConfig(p=cfg["p"], placement=cfg["placement"],
                       n_samples=cfg["n_samples"], seed=mc_seed)
    family = model(cfg)
    init, mcfg = family.build(cfg, mc)
    params = jax.jit(init, static_argnums=1)(jax.random.key(w_seed), mcfg)
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(chips)
    n = int(traffic["sessions"])
    options = (family.engine_options(cfg, traffic)
               if hasattr(family, "engine_options") else {})
    return StreamingEngine(params, mcfg, backend="pallas_seq",
                           max_sessions=int(traffic["max_sessions"]),
                           chunk_capacity=int(traffic["chunk_capacity"]),
                           max_pending=n, mesh=mesh, **options)


class CompileCounter:
    """Counts executables built or loaded while :attr:`on` is set."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Run:
    """What one run recorded; the metric readers take their numbers here."""

    cell: Cell
    seconds: float
    window: tuple[float, float]     # host clock, start and end
    due: np.ndarray                 # per chunk served after set-up
    submit: np.ndarray
    done: np.ndarray
    free: np.ndarray                # when the loop was last free before it
    session: np.ndarray             # the session it came from
    index: np.ndarray               # its entry in the session's schedule
    steps: np.ndarray               # the steps it carried
    schedule: loadgen.Schedule      # what the generator drew
    ticks: list                     # (start, end, chunks) per window tick
    tick_metrics: list              # the engine's TickMetrics, window ticks
    compiles_in_window: int
    setup_s: float
    device_kind: str
    chips: int
    memory_peak_bytes: int
    trace: dict | None = None
    failed: int = 0
    setup_phases: dict = dataclasses.field(default_factory=dict)

    @property
    def in_window(self) -> np.ndarray:
        w0, w1 = self.window
        return (self.due >= w0) & (self.due < w1)


class _Spans:
    """``TraceAnnotation`` spans while :attr:`on`; nothing otherwise."""

    def __init__(self):
        import jax

        self.on = False
        self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name):
        return self._ann(name) if self.on else contextlib.nullcontext()


def _wait_until(t: float, clock=time.perf_counter):
    while (left := t - clock()) > 0:
        time.sleep(left - 0.0005 if left > 0.001 else 0)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, require_chip: bool = True,
        cell: Cell | None = None, controls=(), verify: bool = True,
        warmed: set | None = None) -> tuple[Run, dict]:
    """Run a cell once.  Returns the record and the check's numbers.

    The program's matmuls run at the configuration's ``dtype`` (JAX's
    default matmul precision: ``"float32"`` is float32 products, where a
    TPU would otherwise take one bfloat16 pass).  ``controls`` are matmul
    precisions of the reference to put in the program's place as well;
    their numbers come back under ``control:<precision>``.  Without
    ``verify`` (the knee sweep) the reference does not run.  ``warmed``
    holds tick sizes an earlier run in this process already compiled at
    the same launch shape; they are not warmed again, and this run's are
    added.
    """
    import jax

    cell = cell or load_cell(name)
    with jax.default_matmul_precision(cell.cfg["dtype"]):
        return _run(cell, seed, seconds, trace, t_start=t_start,
                    require_chip=require_chip, controls=controls,
                    verify=verify, warmed=warmed)


def _run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start,
         require_chip, controls, verify, warmed) -> tuple[Run, dict]:
    t_start = time.perf_counter() if t_start is None else t_start
    chips = int(cell.workload["chips"])
    import jax

    from repro.launch import compile_cache
    from repro.serve import prewarm

    devices = check_chips(chips) if require_chip else jax.devices()[:chips]

    compile_cache.enable()
    phases = {"start": time.perf_counter() - t_start}
    cfg, traffic = cell.cfg, cell.traffic
    w_seed, mc_seed, t_seed = derive_seeds(seed)
    rng = np.random.default_rng(t_seed)
    bank = model(cfg).chunk_bank(rng, cfg, traffic)
    warm = float(traffic["warmup_s"])
    sched = generator(traffic).schedule(traffic, rng, warm + seconds)
    n = int(traffic["sessions"])
    sids = [f"s{i:04d}" for i in range(n)]
    counter = CompileCounter()

    mark = time.perf_counter()

    def phase(name):
        nonlocal mark
        now = time.perf_counter()
        phases[name], mark = now - mark, now

    engine = build_engine(cfg, traffic, w_seed, mc_seed, chips)
    for sid in sids:
        engine.admit(sid)
    phase("build")
    prewarm(engine)
    phase("prewarm")
    spans = _Spans()
    history = [[] for _ in range(n)]     # (bank row, steps) served, in order
    served = [[] for _ in range(n)]      # host summaries, in order
    failed = 0

    def tick(idx, rows, lens):
        """Serve bank ``rows`` (their first ``lens`` steps) to ``idx``."""
        nonlocal failed
        with spans("submit"):
            chunks = {sids[i]: bank[b][:m]
                      for i, b, m in zip(idx, rows, lens)}
        try:
            with spans("step"):
                res = engine.step(chunks)
            with spans("block"):
                host = jax.device_get({s: r.summary for s, r in res.items()})
        except Exception as err:  # a failed tick fails its chunks, no more
            print(f"tick failed: {type(err).__name__}: {err}",
                  file=sys.stderr)
            failed += len(idx)
            return False
        for i, b, m in zip(idx, rows, lens):
            history[i].append((b, m))
            served[i].append(host[sids[i]]._asdict())
        return True

    warmed = set() if warmed is None else warmed
    for k in range(n, 0, -1):             # every tick size, largest first
        if k not in warmed:
            tick(list(range(k)), rng.integers(0, len(bank), k), [None] * k)
            warmed.add(k)
    phase("tick_sizes")

    if trace:
        # Starting the profiler stalls the host for a second or more: start
        # it before the traffic, and record spans from the window's start.
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=_trace_options())
    clock = time.perf_counter
    nxt = np.zeros(n, int)
    next_due = np.array([d[0] if d.size else np.inf for d in sched.due])
    recs, ticks, tmetrics = [], [], []
    t0 = clock()
    w0, w1 = t0 + warm, t0 + warm + seconds
    next_due += t0
    setup_s = w0 - t_start
    phases["traffic_warmup"] = warm
    free = t0
    while True:
        now = clock()
        if now >= w0 and not counter.on:
            counter.on, spans.on = True, trace
        ready = np.flatnonzero(next_due <= now)
        if ready.size == 0:
            if not np.isfinite(next_due).any():
                break
            until = next_due.min()
            if now < w0:
                until = min(until, w0)
            with spans("arrive_wait"):
                _wait_until(until)
            continue
        due = next_due[ready]
        rows = [sched.chunks[i][nxt[i]] for i in ready]
        lens = [sched.lengths[i][nxt[i]] for i in ready]
        submit = clock()
        ok = tick(list(ready), rows, lens)
        done = clock()
        if w0 <= submit < w1:
            ticks.append((submit, done, len(ready)))
            tmetrics.append(engine.last_metrics)
        for i, d, b, m in zip(ready, due, rows, lens):
            j = nxt[i]
            recs.append((d, submit, done if ok else np.inf, free, i, j,
                         len(bank[b][:m])))
            nxt[i] = j = j + 1
            if j < sched.due[i].size:   # its time, or this result + delay
                t = max(sched.due[i][j] + t0, done + sched.after[i][j])
                next_due[i] = t if t < w1 else np.inf
            else:
                next_due[i] = np.inf
        free = done
    counter.on = spans.on = False
    if trace:
        jax.profiler.stop_trace()
    rec = np.array(recs, float).reshape(-1, 7)
    mem = _peak_bytes(devices)
    kind = devices[0].device_kind
    del engine
    gc.collect()

    result = Run(cell=cell, seconds=float(seconds), window=(w0, w1),
                 due=rec[:, 0], submit=rec[:, 1], done=rec[:, 2],
                 free=rec[:, 3], session=rec[:, 4].astype(int),
                 index=rec[:, 5].astype(int), steps=rec[:, 6].astype(int),
                 schedule=sched, ticks=ticks, tick_metrics=tmetrics,
                 compiles_in_window=counter.count, setup_s=setup_s,
                 device_kind=kind, chips=chips, memory_peak_bytes=mem,
                 failed=failed, setup_phases=phases)
    if trace and _traced(TRACE_DIR):
        result.trace = trace_reduce.reduce_dir(TRACE_DIR, chips=chips,
                                               spans=SPANS)
    numbers = (check(cell, w_seed, mc_seed, bank, history, served,
                     controls=controls) if verify else {})
    return result, numbers


def _trace_options():
    """Host TraceMe spans and device activity; no Python call tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _traced(path: Path) -> bool:
    return any(path.rglob("*.xplane.pb"))


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def check(cell: Cell, w_seed: int, mc_seed: int, bank, history, served, *,
          controls=()) -> dict:
    """The numbers compared against their limits, from the reference.

    Each configuration's reference module defines ``replay`` and ``gaps``;
    its file's ``limits`` give the limit of each number.  A chunk that was
    never served, or a value that is not finite, fails on its own.
    ``history`` holds each session's served ``(bank row, steps)`` pairs,
    ``steps`` None for a whole row.
    """
    ref_mod = reference(cell.cfg)
    chunks = served_chunks(bank, history)
    ref = ref_mod.replay(cell.cfg, w_seed, mc_seed, chunks)
    reset = ref_mod.replay(cell.cfg, w_seed, mc_seed, chunks, carry=False)
    numbers = dict(ref_mod.gaps(served, ref, reset))
    numbers["nonfinite"] = float(sum(
        not np.isfinite(v).all() for s in served for c in s
        for v in c.values()))
    for matmul in controls:
        ctl = ref_mod.replay(cell.cfg, w_seed, mc_seed, chunks, matmul)
        for k, v in ref_mod.gaps(ctl, ref, reset).items():
            numbers[f"control:{matmul}:{k}"] = v
    return numbers


def served_chunks(bank, history) -> list:
    """Each session's chunks, in order, as the reference takes them: one
    array of bank rows where every chunk is a whole row, else a list of
    the chunks as served."""
    if all(m is None for h in history for _, m in h):
        return [bank[np.asarray([b for b, _ in h], int)] for h in history]
    return [[bank[b][:m] for b, m in h] for h in history]


def limits(cell: Cell) -> dict:
    """The limit of each number compared; a configuration without its
    limits measured cannot decide ``correct``."""
    if cell.cfg.get("limits") is None:
        raise ValueError(f"configuration {cell.cfg['name']!r} has no "
                         "limits measured")
    return {**cell.cfg["limits"], "nonfinite": 0.0}


def is_correct(run_: Run, numbers: dict) -> bool:
    lim = limits(run_.cell)
    served = np.isfinite(run_.done[run_.in_window]).all()
    return (served and run_.failed == 0
            and all(numbers[k] <= v for k, v in lim.items()))


def read_metrics(run_: Run, section: str) -> dict:
    """Every metric of ``section`` this cell reports, by its reader."""
    out = {}
    for m in run_.cell.spec[section]:
        if run_.cell.name not in m.get("workloads", [run_.cell.name]):
            continue
        value = _module("metrics", m["name"]).read(run_)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(run_: Run, numbers: dict, trace: bool) -> dict:
    import jax

    lim = limits(run_.cell)
    attempted = int(run_.in_window.sum())
    missing = int((~np.isfinite(run_.done[run_.in_window])).sum())
    device = {"platform": jax.devices()[0].platform,
              "kind": run_.device_kind, "count": run_.chips,
              "memory_peak_bytes": run_.memory_peak_bytes}
    line = {"correct": is_correct(run_, numbers), "attempted": attempted,
            "failed": missing,
            "metrics": read_metrics(run_, "per_layer" if trace
                                    else "end_to_end"),
            "device": device}
    if trace and run_.trace is not None:
        device["busy_s"] = run_.trace["busy_s"]
        device["window_s"] = run_.trace["window_s"]
        line["breakdown"] = {"device_ops": run_.trace["device_ops"],
                             "idle_gaps": run_.trace["idle_gaps"]}
    line["check"] = {k: {"value": numbers[k], "limit": v}
                     for k, v in lim.items()}
    return line


def launch_shapes(cell: Cell) -> list[tuple[int, int, int, int]]:
    """``(rows, T, I, H)`` of each kernel launch of one tick."""
    t = cell.traffic
    slots = -(-int(t["max_sessions"]) // int(cell.workload["chips"]))
    rows = slots * int(cell.workload["chips"]) * int(cell.cfg["n_samples"])
    return [(rows, int(t["chunk_capacity"]), i, h)
            for i, h in model(cell.cfg).layer_widths(cell.cfg)]
