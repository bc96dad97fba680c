"""``decode_hbm_share`` for a LOOPED stack: least time by HBM bandwidth for
the window's decode work over the time the device was busy, both scaled to
the window.  Each decode step reads the layers' matrices ``total_ut_steps``
times (once a pass) and the head once; the steps together read K and V of
every context position each emitted token attended to, in every one of the
``num_hidden_layers x total_ut_steps`` cache layers (``lib/flops_ouro.py``).
Busy time is the trace's busy share times the window.  Prefill's bytes are
not counted, so the share reads low by the fill stage's part of the busy
time.  The share of the whole step that bounds later claims in this cell."""

from benchmark.lib import flops_ouro


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or "loop_shape" not in c or c["decode_chunks"] <= 0:
        return None
    least = flops_ouro.decode_min_seconds(
        ctx.config["hf_config"],
        decode_steps=c["decode_chunks"] * c["chunk_size"],
        context_token_reads=c["context_token_reads"],
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
