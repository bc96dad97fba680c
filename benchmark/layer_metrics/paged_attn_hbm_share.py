"""The paged decode kernel against the HBM roofline: the least bytes its
executions in the traced slice must read, over the chip's bandwidth, over
their device seconds.

One execution of ``paged_attn_decode*`` is one layer of one decode step
and reads at least K and V of every position its rows attend to:
``ctx_tokens_sum`` of the ``areal.engine.decode.dispatch`` span that
dispatched its chunk, times the bytes of one cached position in one layer
(``lib/flops.kv_bytes_per_token``).  Base: the slice's executions, each
matched with the MEAN ``ctx_tokens_sum`` of the slice's dispatch spans (a
chunk runs a ring's depth after its dispatch, so the two cannot be paired
one to one; consecutive chunks differ by a chunk's tokens a row).  The
host's context at dispatch leaves out the tokens still in the ring and
those the chunk itself adds; a row that ends inside a chunk is counted to
the chunk's end.  Queries, tables and outputs are not counted."""

from benchmark.lib import flops, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    dispatches = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if "ctx_tokens_sum" in s.counts
    ]
    if not dispatches:
        return None
    calls, seconds, chips = span_reduce.kernel_calls(t, "paged_attn_decode")
    if calls <= 0 or seconds <= 0:
        return None
    ctx_mean = sum(s.counts["ctx_tokens_sum"] for s in dispatches) / len(dispatches)
    # under tensor parallelism each chip reads its own heads' share
    bytes_a_call = ctx_mean * flops.kv_bytes_per_token(ctx.config["hf_config"], 1) / chips
    least = calls * bytes_a_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
