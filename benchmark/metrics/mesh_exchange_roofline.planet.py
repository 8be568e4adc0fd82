"""The exchange's share of the interconnect roofline, in %: the least
bytes it must move (``exchange.least_bytes`` of the profiled job's
``mesh_route_remote_lanes_total``) over the chips' peak interconnect
bandwidth, over the device time under ``mesh.route`` (the largest over
the chips)."""

from benchmark import exchange


def read(run):
    prof = run.get("profile")
    route = prof and prof.get("scope_s", {}).get("mesh.route")
    counts = [e for e in run.get("events") or ()
              if e.get("event") == "mesh_route"]
    if not route or not counts:
        return None
    return exchange.roofline_pct(
        exchange.least_bytes(counts[0]["remote_lanes"]), prof["mesh_chips"],
        route, exchange.ici_bytes_per_s(prof["device_kind"]))
