"""Source rows of every job the window ran, over the time from the
window's start to the end of its last job (host clock)."""


def read(run):
    return run["rows"] / run["window_s"] if run["rows"] else None
