"""One file per kind of window; ``run.py`` finds it by the ``driver`` named
in the cell's workload file. A driver exposes ``run(cell) -> record``."""
