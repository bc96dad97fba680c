"""Device time of the first halves of the layers that attend the pool of
whole-context pages: the regions ``areal.attn`` (the one full-attention
layer: norm, q/k/v, the paged kernel and the merge with the chunk's own
tokens, the pairs' difference and norm, output projection, residual add)
and ``areal.attn.cross`` (each cross layer: the same with queries only), in
every program, over device busy time in the traced slice
(``lib/region_reduce.py``).  The window layers' halves keep
``areal.attn.window``, which a region's name takes with it
(``areal.attn`` holds everything under it) and is taken off here.  A
program without a cross region is not this stack: nothing is read."""

from benchmark.lib import region_reduce


def value(ctx):
    cross = region_reduce.share(ctx, regions=("areal.attn.cross",))
    if not cross:
        return None
    under = region_reduce.share(ctx, regions=("areal.attn",))
    window = region_reduce.share(ctx, regions=("areal.attn.window",)) or 0.0
    return under - window
