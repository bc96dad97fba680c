"""How much of the paged KV pool holds live rows: the mean of
``pages_live / pages_total`` over the ``areal.engine.ensure_blocks`` spans
of the traced slice (once a step, before the dispatch; a short span, so
the slice has one even where it holds no whole step).  Live: blocks
referenced by rows that decode or fill, each once; parked rows and the
prefix cache's holdings are not live."""

from benchmark.lib import span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    shares = [
        s.counts["pages_live"] / s.counts["pages_total"]
        for s in (span_reduce.named(t, "areal.engine.ensure_blocks") if t else [])
        if s.counts.get("pages_total", 0) > 0 and "pages_live" in s.counts
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
