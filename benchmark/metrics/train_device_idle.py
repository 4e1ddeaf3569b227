"""1 - the union of device-op intervals over the traced window."""
from ..lib import xplane


def read(record):
    return xplane.idle_percent(record["trace"]) if record["trace"] else None
