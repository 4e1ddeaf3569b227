"""Sub-tiles of the whole score square that the flash kernels run under a
sliding window, over all of them: ``run / total`` of the ``flash_tiles``
events the program left while its step was traced (those whose ``mask`` is
``window``). 1.0 is a walk that skips nothing; a window of 1,024 keys at
8,192 tokens and sub-tile 128 runs 0.132 of the square. ``None`` where the
program left no such event."""


def read(record):
    tiles = [e for e in record["window"].get("flash_tiles") or ()
             if e.get("mask") == "window"]
    total = sum(e["total"] for e in tiles)
    return sum(e["run"] for e in tiles) / total if total else None
