"""Sub-tiles of the stream's whole score square that the flash kernels run
under the two-stream block mask, over all of them: ``run / total`` of the
``flash_tiles`` events the program left while its step was traced (those
whose ``mask`` is ``block_diffusion``). 1.0 is a walk that skips nothing;
the mask's own share at sub-tile 128 and 8,192 tokens is 0.258. ``None``
where the program left no such event."""


def read(record):
    tiles = [e for e in record["window"].get("flash_tiles") or ()
             if e.get("mask") == "block_diffusion"]
    total = sum(e["total"] for e in tiles)
    return sum(e["run"] for e in tiles) / total if total else None
