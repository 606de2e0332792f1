"""Items over batch slots (batches x ``max_batch``) of the device steps
the scheduler ran in the window, from ``stats()`` counters."""


def read(run):
    st = run.get("stats")
    if st is None or st["batches"] == 0:
        return None
    return st["items"] / (st["batches"] * run["max_batch"])
