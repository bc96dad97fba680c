"""The latent decode kernel against its roofline: the least time its
executions in the traced slice need, by bytes over the chip's bandwidth or
by operations over its peak, whichever is larger, over their device
seconds.

One execution of ``paged_mla_decode*`` is one layer of one decode step and
reads at least the latent entry of every position its rows attend to
(``latent_ctx_tokens_sum`` of the ``areal.engine.decode.dispatch`` span
that dispatched its chunk, times ``lib/flops_mla.latent_bytes_per_token``)
and multiplies every head's query and probabilities with it
(``mla_flops_per_token``): 121 FLOP/B at the published sizes, under the
ridge, so the bytes decide.  Base: the slice's executions, each matched
with the MEAN count of the slice's dispatch spans (a chunk runs a ring's
depth after its dispatch, so the two cannot be paired one to one).  The
host's context at dispatch leaves out the tokens still in the ring and
those the chunk itself adds, so the share reads low by a few percent; a
row that ends inside a chunk is counted to the chunk's end.  Queries,
tables and outputs are not counted, nor the 64 columns of padding a
stored row carries."""

from benchmark.lib import flops_mla, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    dispatches = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if "latent_ctx_tokens_sum" in s.counts
    ]
    if not dispatches:
        return None
    calls, seconds, _ = span_reduce.kernel_calls(t, "paged_mla_decode")
    if calls <= 0 or seconds <= 0:
        return None
    ctx_mean = sum(s.counts["latent_ctx_tokens_sum"] for s in dispatches) / len(dispatches)
    least = calls * flops_mla.mla_kernel_min_seconds(
        ctx.config["hf_config"], ctx_mean, ctx.peaks
    )
    return 100.0 * least / seconds
