"""Device time of the FULL-attention layers' first halves in the trainer's
step (the region ``areal.attn`` less what lies under ``areal.attn.window``:
norm, q/k/v and their rope tables, the flash kernels, the gate, output
projection, residual add), forward, backward and recomputed, over device
busy time in the traced slice (``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce


def value(ctx):
    both = region_reduce.share(ctx, regions=("areal.attn",))
    if not both:
        return None  # a program without the regions: nothing
    return both - (region_reduce.share(ctx, regions=("areal.attn.window",)) or 0.0)
