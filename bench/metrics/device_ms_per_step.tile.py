"""Device busy milliseconds in the traced window per serve step the
scheduler ran in it."""


def read(run):
    if run.get("trace") is None or not run.get("stats", {}).get("batches"):
        return None
    return run["trace"]["busy_s"] / run["stats"]["batches"] * 1e3
