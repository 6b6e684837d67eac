"""Share of the traced window in which no operation ran on the device."""


def read(run):
    t = run.trace
    if not t or not t["window_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
