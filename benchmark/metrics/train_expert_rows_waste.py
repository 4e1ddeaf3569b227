"""What the static row buffer costs: ``expert_rows`` (rows of the buffer the
grouped products were given, passes x the buffer's rows) over
``routed_slots``, minus 1, over the window's steps."""


def read(record):
    w = record["window"]
    routed, rows = w.get("routed_slots"), w.get("expert_rows")
    if not routed or not rows:
        return None
    return sum(map(sum, rows)) / max(sum(map(sum, routed)), 1) - 1.0
