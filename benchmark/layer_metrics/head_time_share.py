"""Device time of what turns a step's hidden states into its tokens (the
regions ``areal.head``: final norm and the logits product, and
``areal.sample``: sampler, log-probability, stop rule, the rows'
bookkeeping), in every program, over device busy time in the traced
slice (``lib/region_reduce.py``)."""

from benchmark.lib import region_reduce

REGIONS = ("areal.head", "areal.sample")


def value(ctx):
    return region_reduce.share(ctx, regions=REGIONS)
