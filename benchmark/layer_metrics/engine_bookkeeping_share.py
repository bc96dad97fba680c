"""Host bookkeeping of the engine's thread: self time of
``areal.engine.step`` (what no child span covers) and of its ``admit``,
``fill.dispatch``, ``fill.activate``, ``ensure_blocks``,
``decode.dispatch`` and ``harvest.fold`` children, over the traced slice
(from the first to the last thing the trace saw; the generation server
polls without a pause, so that is the union of its ``areal.gserver.poll``
spans, which the slice's edges cut).  No blocked wait is in it: the
first-token fetch and the harvest's wait and fetch are spans of their
own.  A phase that an edge of the slice cut counts for the part inside it
(``span_reduce.with_cut_phases``)."""

from benchmark.lib import span_reduce


def value(ctx):
    return span_reduce.share_of_engine_thread(ctx, span_reduce.BOOKKEEPING)
