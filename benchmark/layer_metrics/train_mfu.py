"""Model FLOP/s utilisation of the window: forward + backward FLOPs of the
real tokens (``benchmark/lib/flops.py``; rematerialised work not counted)
over the window's seconds over the chips' published bf16 peak
(``benchmark/lib/peaks.py``)."""


def value(ctx):
    c = ctx.window["counters"]
    peak = ctx.peaks["bf16_flops"] * ctx.n_devices
    return 100.0 * c["train_flops"] / c["window_s"] / peak
