"""Engine runtime: as `compiles_in_window`, over the script cell's window."""

from benchmark.layers.compiles_in_window import read  # noqa: F401
