"""Python runtime: milliseconds of the window that the served process
spent in full (generation 2) garbage collections, by the host clock
(``gc.callbacks``).  Every thread of the process, the engine's too,
stalls while one runs."""


def read(run):
    return run.window["gc_full_s"] * 1e3
