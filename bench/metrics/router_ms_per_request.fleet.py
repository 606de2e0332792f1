"""The router's own milliseconds per request it admitted in the window
(admission, route key, pick and bookkeeping; the replicas' ``submit``
left out), from its exact ``route_s`` counter."""


def read(run):
    if run.get("route_s") is None or not run.get("admitted"):
        return None
    return run["route_s"] / run["admitted"] * 1e3
