"""setup_s: seconds from process start to the first request of the
traffic (weights from the seed, engine, every prefill length warmed)."""


def read(run, name):
    return run["setup_s"]
