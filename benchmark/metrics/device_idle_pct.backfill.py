"""Share of one whole job in which no operation ran on the device, in
%: one minus the union of the device's busy intervals over the job's
span, from the profiler trace."""


def read(run):
    prof = run.get("profile")
    if not prof or not prof["n_ops"] or prof["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / prof["window_s"])
