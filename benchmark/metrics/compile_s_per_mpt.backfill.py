"""Seconds in JAX's compile phases inside the window, per million source
rows: the program's ``jax.compile`` spans (trace, lower and backend
phases; a program loaded from the persistent cache included) over the
window's jobs. A program that records no compile spans reports
nothing."""

from benchmark import spans


def read(run):
    records = run.get("spans")
    if not records or not run["rows"] or not _records_compiles():
        return None
    return spans.total(records, {"jax.compile"}) / (run["rows"] / 1e6)


def _records_compiles() -> bool:
    from heatmap_tpu.obs import tracing

    return getattr(tracing, "COMPILE_SPAN", None) == "jax.compile"
