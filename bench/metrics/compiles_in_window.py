"""Executables compiled or loaded from the cache while the window ran."""


def read(run):
    return float(run.compiles_in_window)
