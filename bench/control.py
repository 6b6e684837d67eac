"""Readings that set a cell's limits: the program's, the control's, faults'.

    python bench/control.py --workload clf_icu_pod16 --seeds 1,2,3 \\
        --seconds 51 --fault-seeds 3 --fault-seconds 10

For each seed, one run of the cell at its own size, load and window (the
tick sizes are warmed once, for the first seed), then the comparison with
the float32 reference twice: of what the program served, and of the
control, the same reference with every matmul at ``Precision.HIGH`` (three
bfloat16 passes) put in the program's place.  Then, on the first
``--fault-seeds`` seeds, one run with each fault of ``bench/faults.py``
planted in the program.  Prints one JSON line per run and a last line
with, per number, the largest program reading (the lower end of its
limit), the smallest control reading (the upper end) and the smallest
reading of each fault.  The benchmark's own runs never run any of these.
"""

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL = "high"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--fault-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import faults, harness

    seeds = [int(s) for s in args.seeds.split(",")]
    lowest = {}       # reading -> number -> least (program: largest)
    warmed = set()

    def note(who, numbers, pick):
        acc = lowest.setdefault(who, {})
        for k, v in numbers.items():
            acc[k] = pick(acc.get(k, v), v)

    def one(seed, seconds, controls=(), fault=None):
        with faults.FAULTS[fault]() if fault else contextlib.nullcontext():
            run, numbers = harness.run(args.workload, seed, seconds, False,
                                       controls=controls, warmed=warmed)
        print(json.dumps({"seed": seed, "fault": fault,
                          "attempted": int(run.in_window.sum()),
                          "correct": harness.is_correct(run, numbers),
                          "numbers": numbers}), flush=True)
        return numbers

    for seed in seeds:
        numbers = one(seed, args.seconds, controls=(CONTROL,))
        prefix = f"control:{CONTROL}:"
        note("program_max", {k: v for k, v in numbers.items()
                             if not k.startswith("control:")}, max)
        note("control_min", {k[len(prefix):]: v for k, v in numbers.items()
                             if k.startswith(prefix)}, min)
    for seed in seeds[:args.fault_seeds]:
        for fault in faults.FAULTS:
            note(f"{fault}_min", one(seed, args.fault_seconds, fault=fault),
                 min)
    print(json.dumps({"workload": args.workload, **lowest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
