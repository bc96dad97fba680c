"""The decode step's state-update kernel against the HBM roofline: the
least bytes its executions in the traced slice must move, over the chip's
bandwidth, over their device seconds.

One execution of ``ssm_state_update*`` is one Mamba layer of one decode
step and reads and writes the float32 state of every LIVE row (dead slots
are skipped): ``2 x 4 B x d_state x d_inner`` a row
(``lib/flops_hybrid.ssm_update_min_bytes``).  Live rows an execution:
the tokens of the chunks FOLDED inside the slice over their steps (count
``tokens`` of the spans ``areal.engine.harvest.fold``: one token a live
row a step), which are the chunks before those the slice's kernels ran
in; where the slice folded none, the window's tokens over its decode
steps.  (Not the window's mean alone: with the server full the rows
alive drift by a fifth over a window, and a slice of 3 s that lands on
fewer rows than the mean reads high by as much; nor the rows of a
dispatch's snapshot, which counts a row that ends inside a chunk to the
chunk's end.)  Decay, input, B, C and the output (under 1% of the state)
are not counted."""

from benchmark.lib import flops_hybrid, span_reduce


def value(ctx):
    c = ctx.window["counters"]
    steps = c["decode_chunks"] * c["chunk_size"]
    t = span_reduce.spans_of(ctx)
    if not t or "layer_types" not in c or steps <= 0:
        return None
    calls, seconds, _ = span_reduce.kernel_calls(t, "ssm_state_update")
    if calls <= 0 or seconds <= 0:
        return None
    folded = [
        s.counts["tokens"]
        for s in span_reduce.named(t, "areal.engine.harvest.fold")
        if s.counts.get("tokens", 0) > 0
    ]
    live = (
        sum(folded) / (len(folded) * c["chunk_size"])
        if folded else c["tokens_emitted"] / steps
    )
    least = calls * flops_hybrid.ssm_update_min_bytes(
        ctx.config["hf_config"], live
    ) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
