"""Engine runtime: as `xla_compiles_in_window`, over the script cell's window."""

from benchmark.layers._program_spans import xla_compiles as read  # noqa: F401
