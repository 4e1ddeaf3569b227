"""All tokens of all steps finished in the window, over the whole window."""


def read(record):
    w = record["window"]
    return w["tokens"] / w["seconds"] if w.get("tokens") else None
