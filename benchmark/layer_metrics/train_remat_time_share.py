"""Device time of the forward work the backward pass does again (the
operations whose scope path holds ``rematted_computation``, whatever
their region), over device busy time in the traced slice
(``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce


def value(ctx):
    return region_reduce.share(ctx, passes=(region_reduce.REMAT,))
