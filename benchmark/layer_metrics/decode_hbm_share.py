"""Least time by HBM bandwidth for the window's decode work over the time
the device was busy, both scaled to the window: each decode step reads the
weights once (every layer's matrices and the head, bf16) and the steps
together read the KV of every context position each emitted token attended
to (``benchmark/lib/flops.py``; the contexts are those of the sequences
that completed in the window, scaled to the tokens generated in it).  Busy time is the trace's busy share times
the window.  Prefill's bytes are not counted (it is compute-bound and a few
percent of the time), so the share reads a little low."""

from benchmark.lib import flops


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or c["decode_chunks"] <= 0:
        return None
    least = flops.decode_min_seconds(
        ctx.config["hf_config"], c["n_layers"],
        decode_steps=int(c["decode_chunks"] * c["chunk_size"]),
        context_token_reads=int(c["context_token_reads"]),
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
