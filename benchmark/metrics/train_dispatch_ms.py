"""The host's share of a step: the benchmark's clock around ``step(x, y)``
until it returns, before any block; mean over the window's steps."""


def read(record):
    d = record["window"].get("dispatch_s")
    return 1e3 * sum(d) / len(d) if d else None
