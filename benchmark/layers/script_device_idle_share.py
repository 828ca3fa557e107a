"""Device: as `device_idle_share`, over the traced studies of the script cell."""

from benchmark.layers.device_idle_share import read  # noqa: F401
