"""Device busy milliseconds in the traced window, summed over the
fleet's chips, per serve step its replicas ran in it (summed over
replicas)."""


def read(run):
    steps = sum((run.get("replica_steps") or {}).values())
    if run.get("trace") is None or not steps:
        return None
    return sum(run["trace"]["busy_by_device"].values()) / steps * 1e3
