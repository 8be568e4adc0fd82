"""Wall time from the cascade's dispatch to its outputs being ready, in
seconds per million source rows: the ``cascade.dispatch.device`` stage
that ``obs.DispatchTimer`` records when the event log is on."""


def read(run):
    events = run.get("events")
    if not events or not run["rows"]:
        return None
    device_s = [e["wall_s"] for e in events
                if e.get("event") == "stage_end"
                and e.get("stage") == "cascade.dispatch.device"]
    if not device_s:
        return None
    return sum(device_s) / (run["rows"] / 1e6)
