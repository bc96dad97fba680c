"""The paged decode kernel at ONE query head a KV head (16 KV heads of
128), 192 calls a decode step, against the HBM roofline: the least bytes
its executions in the traced slice must read, over the chip's bandwidth,
over their device seconds.

One execution of ``paged_attn_decode*`` is one CACHE layer (a pass of a
layer) of one decode step and reads at least K and V of every position its
rows attend to: ``ctx_tokens_sum`` of the ``areal.engine.decode.dispatch``
span that dispatched its chunk, times the bytes of one cached position in
one cache layer (``lib/flops_ouro.paged_call_bytes``).  Base and caveats:
``paged_attn_hbm_share.py``'s (the slice's executions against the MEAN
``ctx_tokens_sum`` of its dispatch spans).  Bound by bytes: a call's FLOPs
are 4 x 2,048 a position, 0.25 a byte."""

from benchmark.lib import flops_ouro, span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    dispatches = [
        s for s in (span_reduce.named(t, "areal.engine.decode.dispatch") if t else [])
        if "ctx_tokens_sum" in s.counts
    ]
    if not dispatches or "total_ut_steps" not in ctx.config["hf_config"]:
        return None
    calls, seconds, _chips = span_reduce.kernel_calls(t, "paged_attn_decode")
    if calls <= 0 or seconds <= 0:
        return None
    ctx_mean = sum(s.counts["ctx_tokens_sum"] for s in dispatches) / len(dispatches)
    least = (
        calls * flops_ouro.paged_call_bytes(ctx.config["hf_config"], ctx_mean)
        / ctx.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
