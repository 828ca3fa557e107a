"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest device, read
when the window has closed and before the reference runs."""


def read(ctx):
    if ctx["device"]["platform"] != "tpu":
        return None
    return ctx["device"]["memory_peak_bytes"] or None
