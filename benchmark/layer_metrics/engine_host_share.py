"""The engine's own split of its decode loop, differenced over the window:
``host_s`` over ``host_s + device_s + fetch_s`` of ``timing_split()``.
``device_s`` is the time the host sat blocked on the device, not device
busy time; the device's idle share comes from the trace."""


def value(ctx):
    c = ctx.window["counters"]
    total = c["host_s"] + c["device_s"] + c["fetch_s"]
    return 100.0 * c["host_s"] / total if total > 0 else None
