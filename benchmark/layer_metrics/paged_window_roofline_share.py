"""The windowed decode kernel against its roofline: the least time its
executions in the traced slice need, by bytes over the chip's bandwidth or
by operations over its peak, whichever is larger, over their device
seconds.

One execution of ``paged_window_decode*`` is one WINDOW layer of one decode
step and reads at least K and V of the cached positions inside each row's
window (``window_tokens_sum`` of the ``areal.engine.decode.dispatch`` span
that dispatched its chunk: the sum over its rows of ``min(context,
window)``, times ``lib/flops_window.kv_bytes_per_token``): 7 FLOP/B at the
published sizes, far under the ridge, so the bytes decide.  Base: the
slice's executions, each matched with the MEAN count of the slice's
dispatch spans (a chunk runs a ring's depth after its dispatch, so the two
cannot be paired one to one).  A row past the window reads 4,095 cached
positions where the count says 4,096 (the query's own position is in the
chunk's registers): 0.02% over; a row that ends inside a chunk is counted
to the chunk's end.  Queries, tables and outputs are not counted, nor what
the kernel copies of a window's first page before its first position."""

from benchmark.lib import flops_window, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    dispatches = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if "window_tokens_sum" in s.counts
    ]
    if not dispatches:
        return None
    calls, seconds, _ = span_reduce.kernel_calls(t, "paged_window_decode")
    if calls <= 0 or seconds <= 0:
        return None
    mean = sum(s.counts["window_tokens_sum"] for s in dispatches) / len(dispatches)
    least = calls * flops_window.window_kernel_min_seconds(
        ctx.config["hf_config"], mean, ctx.peaks
    )
    return 100.0 * least / seconds
