"""Set-up: process start to the first timed operation (data, compile or
cache load, warm-up)."""


def read(run):
    return run["setup_s"]
