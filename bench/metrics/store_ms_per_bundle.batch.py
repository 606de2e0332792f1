"""Host milliseconds per committed bundle in the store view's
``bench.store.get`` and ``bench.store.put_result`` spans, read from the
traced window."""


def read(run):
    if run.get("trace") is None or not run.get("commits"):
        return None
    spans = run["trace"]["spans"]
    s = sum(spans.get(n, {}).get("seconds", 0.0)
            for n in ("bench.store.get", "bench.store.put_result"))
    return s / run["commits"] * 1e3 if s > 0 else None
