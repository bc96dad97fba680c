"""Device time of the WINDOW layers' first halves in the trainer's step
(the region ``areal.attn.window``: norm, q/k/v and their rope, the windowed
flash kernels, the gate, output projection, residual add), forward, backward
and recomputed, over device busy time in the traced slice
(``lib/region_reduce.py``).  The full layers' halves keep ``areal.attn``
(``train_full_attn_time_share``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.attn.window",)


def value(ctx):
    share = region_reduce.share(ctx, regions=REGIONS)
    return share if share else None  # a program without the region: nothing
