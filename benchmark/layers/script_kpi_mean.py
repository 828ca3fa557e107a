"""Simulated statistic: as `kpi_mean`, per study of the script cell."""

from benchmark.layers.kpi_mean import read  # noqa: F401
