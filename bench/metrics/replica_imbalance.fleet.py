"""Items the busiest replica served in the window over the mean over
replicas: 1.0 when the router spreads the load evenly."""


def read(run):
    items = list((run.get("replica_items") or {}).values())
    if not items or sum(items) == 0:
        return None
    return max(items) / (sum(items) / len(items))
