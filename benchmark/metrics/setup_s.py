"""Seconds from the process's start to the window's start: imports,
device start-up, the warm job (compiling, or loading every program from
the persistent cache) and making the window's rows (host clock)."""


def read(run):
    return run["setup_s"]
