"""From a JAX profiler trace to the benchmark's device numbers.

Two steps, so the second can be tested on a small recorded trace:

* :func:`extract` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``)
  into plain lists: the harness's host spans (``TraceAnnotation`` events
  on the lines of ``/host:CPU``), and every operation on each
  ``/device:TPU:<n>`` plane's ``XLA Ops`` line by its HLO instruction
  name, marked where it is a Pallas kernel (a ``tpu_custom_call``);
* :func:`reduce` turns those lists into the numbers: the traced window
  (first harness span to last), each device's busy time (the union of its
  operations' intervals inside the window), kernel time and launches, the
  operations that took most time, and the longest idle gaps, each named by
  the harness span that covers most of it.

Times in the extracted lists are nanoseconds from the trace's start, as the
profiler gives them; :func:`reduce` reports seconds.
"""

from __future__ import annotations

import gzip
import json
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def is_kernel(name: str, stats: dict) -> bool:
    """A Pallas kernel: the TPU reports it as a ``tpu_custom_call``."""
    text = " ".join([name, *map(str, stats.values())]).lower()
    return "tpu_custom_call" in text or "custom-call" in text


def op_name(text: str) -> str:
    """``mcd_lstm_seq.1`` from the TPU's ``%mcd_lstm_seq.1 = (...) ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(path, spans) -> dict:
    """Host spans and device operations of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {"spans": [], "devices": {}}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        out["spans"].append([ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns])
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = out["devices"].setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    ops.append([op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                is_kernel(ev.name, stats)])
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: dict, *, chips: int, top: int = 10) -> dict | None:
    """Device numbers of the traced window; None when nothing was traced.

    Only the first ``chips`` devices count (the ones the cell runs on).
    """
    spans = events["spans"]
    devices = sorted(events["devices"])[:chips]
    if not spans or not devices:
        return None
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    busy, kernel_s, kernel_n, by_name = [], [], [], {}
    gaps = []
    for dev in devices:
        ops = [(n, max(s, w0), min(e, w1), k)
               for n, s, e, k in events["devices"][dev] if e > w0 and s < w1]
        merged = _union([(s, e) for _, s, e, _ in ops if e > s])
        busy.append(sum(e - s for s, e in merged))
        kernel_s.append(sum(e - s for _, s, e, k in ops if k) / 1e9)
        kernel_n.append(sum(1 for *_, k in ops if k))
        for n, s, e, _ in ops:
            by_name[n] = by_name.get(n, 0) + (e - s)
        if dev == devices[0]:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    if not any(busy):
        return None
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = [(_cover(spans, s, e), (e - s) / 1e9) for s, e in longest]
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "kernel_s": sum(kernel_s) / len(kernel_s),
        "kernel_launches": kernel_n[0],
        "ticks": sum(1 for n, *_ in spans if n == "step"),
        "device_ops": [[n, t / 1e9 / len(devices)] for n, t in ops_top],
        "idle_gaps": [[n, t] for n, t in named],
    }


def _cover(spans, s, e) -> str:
    """The span overlapping ``[s, e)`` most; ``"untraced"`` where none."""
    best, name = 0, "untraced"
    for n, a, b in spans:
        overlap = min(b, e) - max(a, s)
        if overlap > best:
            best, name = overlap, n
    return name


def reduce_dir(trace_dir: Path, *, chips: int, spans) -> dict | None:
    """:func:`reduce` of the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    return reduce(extract(files[-1], spans), chips=chips)


def load(path) -> dict:
    """Extracted events saved as gzipped JSON (the tests' recorded trace)."""
    with gzip.open(path, "rt") as fh:
        return json.load(fh)

