"""``decode_hbm_share`` for a stack of parallel layers: least time by HBM
bandwidth for the window's decode work over the time the device was busy,
both scaled to the window.  Each decode step reads the weights once (every
layer's matrices and the untied head; the embedding's table is gathered);
every live (row, step) reads and writes the row's recurrent state and conv
tail in EVERY layer (``tokens_emitted``: one token a live row a step); the
steps together read K and V of every context position each emitted token
attended to, in EVERY layer (``lib/flops_parallel.py``).  Busy time is the
trace's busy share times the window.  Prefill's bytes are not counted, so
the share reads low by the fill stage's part of the busy time.  The share
of the whole step that bounds later claims in this cell."""

from benchmark.lib import flops_parallel


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or "parallel_shape" not in c or c["decode_chunks"] <= 0:
        return None
    least = flops_parallel.decode_min_seconds(
        flops_parallel.as_run(ctx.config),
        decode_steps=c["decode_chunks"] * c["chunk_size"],
        row_steps=c["tokens_emitted"],
        context_token_reads=c["context_token_reads"],
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
