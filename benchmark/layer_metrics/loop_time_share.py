"""What LOOPING itself costs: device time in the regions ``areal.loop`` and
``areal.loop.norm`` over device busy time in the traced slice
(``lib/region_reduce.py``).  A region takes an operation whose INNERMOST
scope it is, so the layers' own work inside the loop (``areal.attn``,
``areal.mlp``, ``areal.layers``, ...) is not here: what is, is the outer
scan's own operations (the cache layers' index and the per-pass slices,
the carry) and the norm between passes.  Nothing where the program names
no such region (a stack that does not loop)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.loop",)


def value(ctx):
    t = region_reduce.regions_of(ctx)
    if t is None or region_reduce.seconds_of(t, REGIONS) <= 0:
        return None
    return region_reduce.share(ctx, regions=REGIONS)
