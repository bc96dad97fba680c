"""Device time of the recurrent-state kernels (the decode step's
``ssm_state_update`` and the fill's ``ssm_state_rows``, by the names the
trace gives the Mosaic calls) over device busy time, in the traced
slice."""

from benchmark.lib.trace_reduce import seconds_matching

PATTERN = r"ssm_"


def value(ctx):
    tr = ctx.trace
    if not tr or tr["busy_s"] <= 0:
        return None
    seconds = seconds_matching(tr["op_seconds"], PATTERN)
    return 100.0 * seconds / tr["busy_s"] if seconds > 0 else None
