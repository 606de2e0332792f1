"""XLA compile (or persistent-cache load) seconds during set-up, from the
compile clock."""


def read(run):
    return run["setup_compile"]["compile_s"]
