"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics (``BENCHMARK.json``).  The numbers
that decide ``correct`` are printed beside their limits as the last lines of
standard error and, under ``check``, as the last key of the result, which is
the last line of standard output.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        run, numbers = harness.run(args.workload, args.seed, args.seconds,
                                   bool(args.trace), t_start=T_START)
    except harness.NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 1
    line = harness.result_line(run, numbers, bool(args.trace))
    print("setup phases (s): " + " ".join(
        f"{k}={v:.3f}" for k, v in run.setup_phases.items()),
        file=sys.stderr)
    for k, v in line["check"].items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
