"""Which cache sets a decode step's pace: the recurrent state's bytes over
the state's plus the pages' bytes a step must move, from the counts of the
``areal.engine.decode.dispatch`` spans of the traced slice (their mean):
``state_rows`` (live rows x state layers: each a state and a conv tail
read and written) and ``ctx_tokens_sum`` (the cached positions the rows
attend, in every layer) (``lib/flops_parallel.cache_bytes``).  To lay
beside the two time shares, ``ssm_time_share`` and
``paged_attn_time_share``.  A program whose dispatch spans carry no
``state_rows`` leaves the metric out."""

from benchmark.lib import flops_parallel, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    spans = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if "state_rows" in s.counts and "ctx_tokens_sum" in s.counts
    ]
    if not spans:
        return None
    hf = flops_parallel.as_run(ctx.config)
    b = flops_parallel.cache_bytes(
        hf, sum(s.counts["state_rows"] for s in spans) / len(spans),
        sum(s.counts["ctx_tokens_sum"] for s in spans) / len(spans),
    )
    total = b["state"] + b["pages"]
    return 100.0 * b["state"] / total if total > 0 else None
