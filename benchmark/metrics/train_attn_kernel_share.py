"""Device seconds under op names that hold ``flash_attention_`` (the names
``ops/pallas/flash_attention.py`` gives its three kernels), over the traced
window's busy seconds. A trace with no such name reads ``None``, not zero: a
kernel that lost its name must not pass for a kernel that takes no time."""


def read(record):
    t = record["trace"]
    if not t:
        return None
    spent = [s for name, s in t["op_seconds"].items()
             if "flash_attention_" in name]
    return 100.0 * sum(spent) / t["busy_s"] if spent else None
