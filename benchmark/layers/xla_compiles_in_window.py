"""Engine runtime: XLA compiles inside the window by `jax.monitoring`, the
eager programs of the launch path included; 0 is what a warm window reads."""

from benchmark.layers._program_spans import xla_compiles as read  # noqa: F401
