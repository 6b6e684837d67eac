"""The recurrent kernel's least time over its measured time, from the trace.

Least time per launch is the larger of its FLOPs over peak FLOP/s and its
bytes over HBM bandwidth (``bench/roofline.py``), summed over the launches
the trace holds.  Each tick launches one kernel per layer; a device holds
``rows / chips`` of the launch's rows.
"""

from bench import harness, roofline


def read(run):
    t = run.trace
    if not t or not t["kernel_launches"] or not t["kernel_s"]:
        return None
    shapes = harness.launch_shapes(run.cell)
    least = 0.0
    for rows, T, i, h in shapes:
        rows //= run.chips
        least += roofline.least_time(roofline.lstm_launch_flops(rows, T, i, h),
                                     roofline.lstm_launch_bytes(rows, T, i, h),
                                     run.device_kind)[0]
    ticks = t["kernel_launches"] / len(shapes)
    return least * ticks / t["kernel_s"] * 100.0
