"""How many of the recurrent-state slots hold live rows: the mean of
``state_slots_live / state_slots_total`` over the
``areal.engine.ensure_blocks`` spans of the traced slice (once a step,
before the dispatch).  Live: slots of rows that decode or fill.  A
program without such slots leaves the counts out, and the metric is."""

from benchmark.lib import span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    shares = [
        s.counts["state_slots_live"] / s.counts["state_slots_total"]
        for s in (span_reduce.named(t, "areal.engine.ensure_blocks") if t else [])
        if s.counts.get("state_slots_total", 0) > 0
        and "state_slots_live" in s.counts
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
