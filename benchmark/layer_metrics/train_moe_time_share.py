"""Device time of the trainer's expert layers (the regions ``areal.moe.route``,
``areal.moe.experts`` and ``areal.moe.shared``: the router, the grouped
product over the held experts with its gathers, the shared expert), forward,
backward and recomputed, over device busy time in the traced slice
(``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.moe",)


def value(ctx):
    share = region_reduce.share(ctx, regions=REGIONS)
    return share if share else None  # a program without the regions: nothing
