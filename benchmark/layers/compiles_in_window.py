"""Engine runtime: `CompileTelemetry` entries added inside the window (every
engine records one per runner-cache miss); 0 is what a warm window reads."""


def read(ctx):
    return float(ctx["compiles_in_window"])
