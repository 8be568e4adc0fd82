"""Backend compiles inside the window (``jax.monitoring`` events), per
job the window ran."""


def read(run):
    return run["window_compiles"] / len(run["jobs"]) if run["jobs"] else None
