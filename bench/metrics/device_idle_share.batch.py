"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (job cells)."""


def read(run):
    if run.get("trace") is None or "commits" not in run:
        return None
    return run["trace"]["idle_share"]
