"""90th percentile of submit -> admission inside the engine, from the
engine's log-bucketed digest (``slo_digests()["admission_wait_s"]``,
4 buckets an octave, so good to about 9%), differenced over the window.
Reported as the geometric middle of the bucket that holds the percentile."""

import math


def value(ctx):
    c = ctx.window["counters"]
    counts = c["admission_counts"]
    n = sum(counts)
    if n <= 0:
        return None
    target, cum = math.ceil(0.9 * n), 0
    for i, k in enumerate(counts):
        cum += k
        if cum >= target:
            # bucket i covers (lo * ratio**(i-1), lo * ratio**i]
            i = min(i, len(counts) - 2)
            return 1e3 * c["admission_lo"] * c["admission_ratio"] ** (i - 0.5)
    return None
