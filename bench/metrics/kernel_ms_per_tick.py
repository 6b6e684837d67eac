"""Device time of the Pallas kernels per tick, from the trace."""


def read(run):
    t = run.trace
    if not t or not t["ticks"] or not t["kernel_launches"]:
        return None
    return t["kernel_s"] / t["ticks"] * 1e3
