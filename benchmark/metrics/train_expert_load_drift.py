"""How far the routed load moves inside one window: the largest step's
``routed_slots`` (all expert layers) over the smallest step's, minus 1."""


def read(record):
    routed = record["window"].get("routed_slots")
    if not routed:
        return None
    per_step = [sum(step) for step in routed]
    return max(per_step) / max(min(per_step), 1) - 1.0
