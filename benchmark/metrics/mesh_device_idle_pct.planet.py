"""Share of the profiled job in which a chip ran no operation, in %:
each chip's one minus its busy time over the job's span, averaged over
the cell's chips (profiler trace)."""


def read(run):
    prof = run.get("profile")
    if not prof or not prof["n_ops"] or prof["window_s"] <= 0:
        return None
    return prof["idle_pct"]
