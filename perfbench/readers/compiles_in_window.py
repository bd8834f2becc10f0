"""Programs the daemon's process compiled (or loaded from the persistent
cache) between the window's open and close, as the benchmark's launcher
stamped them. Must read 0."""


def read(obs, params, device):
    return float(len(obs.compiles_in_window))
