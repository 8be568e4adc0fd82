"""Owner-routing imbalance: the most lanes one range shard owns over
the mean, from the program's ``mesh_route`` counts (the plan's exact
``mesh_route_lanes_total{shard}``), the largest over the window's jobs.
A program without the counter reports nothing."""


def read(run):
    ratios = []
    for e in run.get("events") or ():
        if e.get("event") == "mesh_route" and sum(e["lanes"]):
            lanes = e["lanes"]
            ratios.append(max(lanes) / (sum(lanes) / len(lanes)))
    return max(ratios) if ratios else None
