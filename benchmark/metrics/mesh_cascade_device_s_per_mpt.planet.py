"""Device time of the cascade program, the largest over the chips, in
seconds per million source rows of the profiled job (profiler trace)."""


def read(run):
    prof = run.get("profile")
    if not prof or prof.get("cascade_device_s", 0) <= 0 or not prof["rows"]:
        return None
    return prof["cascade_device_s"] / (prof["rows"] / 1e6)
