"""Device time under the mesh program's ``mesh.route`` scope (the owner
sort and the exchange), the largest over the chips, in seconds per
million source rows of the profiled job."""


def read(run):
    prof = run.get("profile")
    route = prof and prof.get("scope_s", {}).get("mesh.route")
    if not route or not prof["rows"]:
        return None
    return route / (prof["rows"] / 1e6)
