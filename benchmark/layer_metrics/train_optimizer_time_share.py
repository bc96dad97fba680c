"""Device time of the region ``areal.optimizer`` (gradient accumulation
and scaling, norm and clip, the optimizer's update and the parameters'),
over device busy time in the traced slice (``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.optimizer",)


def value(ctx):
    return region_reduce.share(ctx, regions=REGIONS)
