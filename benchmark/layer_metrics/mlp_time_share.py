"""Device time of the layers' MLP halves (the regions ``areal.mlp`` and,
in an expert layer, ``areal.moe.route`` / ``.experts`` / ``.shared``
inside it: norm, projections, residual add), in every program, over
device busy time in the traced slice (``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.mlp", "areal.moe")


def value(ctx):
    return region_reduce.share(ctx, regions=REGIONS)
