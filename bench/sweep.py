"""Find a cell's knee on the chip, and size its traffic at 4/5 of it.

    python bench/sweep.py --workload clf_ward_steady --counts 32,48,64,80,96

Steps the session count in one process, each count one run of the cell's
traffic (its generator's parameters, warm-up and chunk length, with
``sessions`` set to the count).  Every count launches at the largest count's
shape (``max_sessions``), so the launch and each tick size compile once for
the whole sweep; the padded rows cost the kernel a little more than the
cell's own shape does, so the knee found is, if anything, low.  Prints per
count the offered and served chunk rates, the p50 and p95 latency, the
backlog (chunks due in the window whose summaries came after it closed) and
the growth of latency (mean of the window's last quarter over its first).
The knee is the highest count whose p95 stays under the generator's
``period_s``, the mean time between one session's chunks, with no growing
backlog: backlog under one chunk per session and growth under 1.5.
``--write`` puts 4/5 of the knee, rounded down to a multiple of the cell's
chips, into the cell's traffic file.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def measure(cell, count: int, top: int, seed: int, seconds: float,
            warmed: set) -> dict:
    import numpy as np

    from bench import harness
    from bench.stats import percentile

    cell.traffic = dict(cell.traffic, sessions=count, max_sessions=top)
    run, _ = harness.run(cell.name, seed, seconds, False, cell=cell,
                         verify=False, warmed=warmed)
    w = run.in_window
    lat = run.done[w] - run.due[w]
    order = np.argsort(run.due[w])
    q = max(1, lat.size // 4)
    w0, w1 = run.window
    return {"sessions": count,
            "offered_per_s": harness.generator(cell.traffic).offered_rate(
                cell.traffic),
            "served_per_s": float(((run.done >= w0) & (run.done <= w1))
                                  .sum()) / seconds,
            "p50_ms": percentile(lat, 50) * 1e3,
            "p95_ms": percentile(lat, 95) * 1e3,
            "backlog": int((run.done[w] > w1).sum()),
            "growth": float(lat[order[-q:]].mean() / lat[order[:q]].mean()),
            "setup_s": run.setup_s}


def knee(rows: list[dict], period_s: float) -> int | None:
    good = [r["sessions"] for r in rows
            if r["p95_ms"] < period_s * 1e3 and r["backlog"] < r["sessions"]
            and r["growth"] < 1.5]
    return max(good) if good else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--counts", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.check_chips(int(cell.workload["chips"]))
    period = harness.generator(cell.traffic).period_s(cell.traffic)
    rows, warmed = [], set()
    counts = sorted(int(c) for c in args.counts.split(","))
    for count in counts:
        rows.append(measure(harness.load_cell(args.workload), count,
                            counts[-1], args.seed, args.seconds, warmed))
        print(json.dumps(rows[-1]), flush=True)
    k = knee(rows, period)
    chips = int(cell.workload["chips"])
    chosen = None if k is None else max(chips, int(0.8 * k) // chips * chips)
    print(json.dumps({"knee": k, "chosen": chosen, "period_s": period}))
    if args.write and chosen is not None:
        path = harness.BENCH / "traffic" / f"{cell.workload['traffic']}.json"
        traffic = json.loads(path.read_text())
        traffic.update(sessions=chosen, max_sessions=chosen)
        path.write_text(json.dumps(traffic, indent=2) + "\n")
    return 0 if k is not None else 1


if __name__ == "__main__":
    sys.exit(main())
