"""``decode_hbm_share`` for a decoder-hybrid-decoder stack: least time by
HBM bandwidth for the window's decode work over the time the device was
busy, both scaled to the window.  Each decode step reads the weights once
(the tied table is the head); the steps together read, for every emitted
token, K and V of every cached position before it ONCE A GLOBAL READER (the
full-attention layer and each cross layer read the same one-layer pool: 8
reads a step), of at most the window's 511 in each window layer, and every
Mamba layer's state and conv tail twice (``lib/flops_sambay.py``).  Busy
time is the trace's busy share times the window.  Prefill's bytes are not
counted, so the share reads low by the fill stage's part of the busy time.
The share of the whole step that bounds later claims in this cell."""

from benchmark.lib import flops_sambay


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or "shared_shape" not in c or c["decode_chunks"] <= 0:
        return None
    least = flops_sambay.decode_min_seconds(
        flops_sambay.as_run(ctx.config),
        decode_steps=c["decode_chunks"] * c["chunk_size"],
        row_steps=c["tokens_emitted"],
        context_token_reads=c["context_token_reads"],
        window_token_reads=c["window_token_reads"],
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
