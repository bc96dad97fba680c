"""Device time of the trainer's loss (the region ``areal.loss``: the
chunked head product with log-probability and entropy, and the PPO
arithmetic; with ``areal.head``, the final norm before it), forward,
backward and recomputed, over device busy time in the traced slice
(``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.loss", "areal.head")


def value(ctx):
    return region_reduce.share(ctx, regions=REGIONS)
