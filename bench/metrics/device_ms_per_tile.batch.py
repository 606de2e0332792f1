"""Device busy milliseconds, summed over the cell's chips, per tile the
job committed in the traced window."""


def read(run):
    if run.get("trace") is None or not run.get("tiles"):
        return None
    busy = sum(run["trace"]["busy_by_device"].values())
    return busy / run["tiles"] * 1e3
