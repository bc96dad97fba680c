"""The decode kernel over the pool of whole-context pages against its
roofline: the least time its executions in the traced slice need, by bytes
over the chip's bandwidth or by operations over its peak, whichever is
larger, over their device seconds.

One execution of ``paged_attn_decode*`` is one GLOBAL READER (the
full-attention layer or a cross layer) of one decode step and reads at
least K and V of every cached position of each row (``ctx_tokens_sum`` of
the ``areal.engine.decode.dispatch`` span that dispatched its chunk, times
``lib/flops_sambay.kv_bytes_per_token``): 3 FLOP/B as the equations state
it, far under the ridge, so the bytes decide.  The span's
``global_readers`` says how many executions a step makes over the one pool
layer (a program without the count is not this stack: nothing is read).
Base: the slice's executions, each matched with the MEAN count of the
slice's dispatch spans (a chunk runs a ring's depth after its dispatch, so
the two cannot be paired one to one); a row that ends inside a chunk is
counted to the chunk's end.  Queries, tables and outputs are not counted."""

from benchmark.lib import flops_sambay, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    dispatches = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if s.counts.get("global_readers", 0) > 0
    ]
    if not dispatches:
        return None
    calls, seconds, _ = span_reduce.kernel_calls(t, "paged_attn_decode")
    if calls <= 0 or seconds <= 0:
        return None
    mean = sum(s.counts["ctx_tokens_sum"] for s in dispatches) / len(dispatches)
    least = calls * flops_sambay.shared_kernel_min_seconds(
        flops_sambay.as_run(ctx.config), mean, ctx.peaks
    )
    return 100.0 * least / seconds
