"""Peak bytes in use on the fullest chip, read after the window
(``device.memory_stats()["peak_bytes_in_use"]``)."""


def value(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
