"""``decode_hbm_share`` for a stack of latent-attention layers: least time
by HBM bandwidth for the window's decode work over the time the device was
busy, both scaled to the window.  Each decode step reads the weights this
chip holds once (every held expert is computed for every row); the steps
together read the latent entry (``kv_lora_rank + qk_rope_head_dim``
values a layer) of every context position each emitted token attended to
(``lib/flops_mla.py``).  Busy time is the trace's busy share times the
window.  Prefill's bytes are not counted, so the share reads a little low.
The share of the whole step that bounds later claims in this cell."""

from benchmark.lib import flops_mla


def value(ctx):
    c, tr = ctx.window["counters"], ctx.trace
    if not tr or "latent_shape" not in c or c["decode_chunks"] <= 0:
        return None
    least = flops_mla.decode_min_seconds(
        ctx.config["hf_config"], c["latent_shape"],
        decode_steps=c["decode_chunks"] * c["chunk_size"],
        context_token_reads=c["context_token_reads"],
        hbm_bytes_per_s=ctx.peaks["hbm_bytes_per_s"],
    )
    busy = tr["busy_s"] / tr["window_s"] * c["window_s"]
    return 100.0 * least / busy if busy > 0 else None
