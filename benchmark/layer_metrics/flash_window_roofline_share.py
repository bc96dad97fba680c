"""The windowed flash kernels against their roofline: the least time their
executions in the traced slice need, by operations over the chip's bf16
peak or by bytes over its bandwidth, whichever is larger, over their device
seconds.

One execution of ``flash_attn_window_{fwd,bwd_dq,bwd_dkv}`` is one sliding
layer of one micro-batch; it has to multiply the (query, key) pairs inside
window, segment and causal mask (``lib/flops_laguna.window_pairs`` of the
window's sequences, a MEAN a micro-batch: the slice's calls cannot be paired
with their micro-batches one to one) 2, 3 and 4 times over
(``flops_laguna.KERNEL_PRODUCTS``), at 64 heads of 128, or move the
micro-batch's row slots AS THE LAYOUT STACKED THEM once (``q``, ``o`` and
their gradients at 64 heads, ``k``, ``v`` and theirs at the 8 KV heads:
``flops_laguna.window_kernel_bytes``).  What the kernels
multiply beside that (the masked part of each block pair they run, padding
slots) is not counted, so the share says how much of their time is the
model's arithmetic."""

from benchmark.lib import flops_laguna, span_reduce


def value(ctx):
    c = ctx.window["counters"]
    if not c.get("window_pairs") or not c.get("microbatches"):
        return None
    t = span_reduce.spans_of(ctx)
    if not t:
        return None
    pairs = c["window_pairs"] / c["microbatches"]
    slots = c["row_slots"] / c["microbatches"]  # as stacked, a mean too
    hf = ctx.config["hf_config"]
    least = seconds = 0.0
    for kernel in flops_laguna.KERNEL_PRODUCTS:
        # "..._fwd" is no prefix of the backward kernels' names
        calls, sec, _ = span_reduce.kernel_calls(t, kernel)
        least += calls * flops_laguna.window_kernel_min_seconds(
            hf, kernel, pairs, slots, ctx.peaks
        )
        seconds += sec
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
