"""Mean seconds a sample's routing RPC (``schedule_request`` to the
GserverManager) took inside the window, on the client's clock: the
benchmark's own span around the call ``PartialRolloutManager`` makes."""


def value(ctx):
    mean = ctx.window["counters"].get("schedule_wait_mean_s")
    return None if mean is None else 1e3 * mean
