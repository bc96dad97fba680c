"""Device time of the paged attention kernel's operations (by the name the
trace gives the Mosaic call) over device busy time, in the traced slice."""

from benchmark.lib.trace_reduce import seconds_matching

PATTERN = r"paged"


def value(ctx):
    tr = ctx.trace
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * seconds_matching(tr["op_seconds"], PATTERN) / tr["busy_s"]
