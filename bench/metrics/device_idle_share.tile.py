"""Share of the traced window in which no operation ran on the device
(tile-service cells)."""


def read(run):
    if run.get("trace") is None or "stats" not in run:
        return None
    return run["trace"]["idle_share"]
