"""Decode and egress, in seconds per million source rows: the program's
spans ``cascade.decode``, ``egress.finalize``, ``egress`` and
``egress.blobs`` over the window's jobs."""

from benchmark import spans

STAGES = {"cascade.decode", "egress.finalize", "egress", "egress.blobs"}


def read(run):
    records = run.get("spans")
    if not records or not run["rows"]:
        return None
    return spans.total(records, STAGES) / (run["rows"] / 1e6)
