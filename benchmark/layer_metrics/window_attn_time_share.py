"""Device time of the WINDOW layers' first halves (the region
``areal.attn.window``: norm, q/k/v, the windowed paged kernel and the merge
with the chunk's own tokens, output projection, residual add), in every
program, over device busy time in the traced slice
(``lib/region_reduce.py``).  The global layers' halves keep ``areal.attn``."""

from benchmark.lib import region_reduce

REGIONS = ("areal.attn.window",)


def value(ctx):
    share = region_reduce.share(ctx, regions=REGIONS)
    return share if share else None  # a program without the region: nothing
