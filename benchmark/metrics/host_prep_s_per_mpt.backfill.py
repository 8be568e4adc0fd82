"""Host stages of the job before the device cascade, in seconds per
million source rows: the program's spans ``ingest.batch``,
``cascade.project`` and ``cascade.emissions``, plus the self time of
``cascade`` (user grouping and vocabulary), over the window's jobs."""

from benchmark import spans

STAGES = {"ingest.batch", "cascade.project", "cascade.emissions"}


def read(run):
    records = run.get("spans")
    if not records or not run["rows"]:
        return None
    host_s = spans.total(records, STAGES) + spans.self_total(records,
                                                             {"cascade"})
    return host_s / (run["rows"] / 1e6)
