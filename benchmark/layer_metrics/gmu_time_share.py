"""Device time of the gated memory units' halves (the region
``areal.gmu``: norm, the gate's projection, the product with the memory,
output projection, residual add), in every program, over device busy time
in the traced slice (``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.gmu",)


def value(ctx):
    share = region_reduce.share(ctx, regions=REGIONS)
    return share if share else None  # a program without the region: nothing
