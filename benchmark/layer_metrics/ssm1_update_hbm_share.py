"""The decode step's Mamba-1 state-update kernel against the HBM roofline:
the least bytes its executions in the traced slice must move, over the
chip's bandwidth, over their device seconds.

One execution of ``ssm_state_update_m1*`` is one Mamba layer of one decode
step and reads and writes the float32 state of every LIVE row (dead slots
are skipped): ``2 x 4 B x d_state x d_inner`` = 655,360 B a row
(``lib/flops_sambay.ssm_update_min_bytes``).  Live rows an execution: the
tokens of the chunks FOLDED inside the slice over their steps (count
``tokens`` of the spans ``areal.engine.harvest.fold``), else the window's
tokens over its decode steps: ``ssm_update_hbm_share`` says why.  ``A``
(one state's size a layer and lane block), dt, the input, B, C and the
output are not counted."""

from benchmark.lib import flops_sambay, span_reduce


def value(ctx):
    c = ctx.window["counters"]
    steps = c["decode_chunks"] * c["chunk_size"]
    t = span_reduce.spans_of(ctx)
    if not t or "shared_shape" not in c or steps <= 0:
        return None
    calls, seconds, _ = span_reduce.kernel_calls(t, "ssm_state_update_m1")
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
    least = calls * flops_sambay.ssm_update_min_bytes(
        flops_sambay.as_run(ctx.config), live
    ) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
