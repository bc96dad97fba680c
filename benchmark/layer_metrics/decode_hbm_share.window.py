"""``decode_hbm_share`` for a stack of window and global attention layers:
least time by HBM bandwidth for the window's decode work over the time the
device was busy, both scaled to the window.  Each decode step reads the
weights this chip holds once (every held expert is computed for every row,
and at 55 rows x 6 pairs over 64 experts every expert is touched); the
steps together read, for every emitted token, K and V of every cached
position before it in each GLOBAL layer and of at most the window's 4,095
in each WINDOW layer (``lib/flops_window.py``).  Busy time is the trace's
busy share times the window.  Prefill's bytes are not counted, so the share
reads low by the fill stage's part of the busy time.  The share of the
whole step that bounds later claims in this cell."""

from benchmark.lib import flops_window


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or "window_shape" not in c or c["decode_chunks"] <= 0:
        return None
    held, vocab = c["window_shape"]
    least = flops_window.decode_min_seconds(
        flops_window.as_run(ctx.config), held, vocab,
        decode_steps=c["decode_chunks"] * c["chunk_size"],
        context_token_reads=c["context_token_reads"],
        window_token_reads=c["window_token_reads"],
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
