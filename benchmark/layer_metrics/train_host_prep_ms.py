"""Host work before a minibatch's program can start: mean per
``areal.train.batch`` span of the traced slice of its ``areal.train.pack``
(split and numpy layout) and ``areal.train.upload`` (placement on the
devices) children."""

from benchmark.lib import span_reduce


def value(ctx):
    return span_reduce.mean_ms_of_children(
        ctx, span_reduce.BATCH, ("areal.train.pack", "areal.train.upload")
    )
