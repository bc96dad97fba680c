"""Padding in the trainer's device layout: 1 - real tokens over padded
[n, B, T] slots, as the engine reports it (``last_padding_frac``) after each
step.  The engine keeps only its last minibatch's figure, so this is the
mean over the window's steps of each step's LAST minibatch."""


def value(ctx):
    return 100.0 * ctx.window["counters"]["pad_frac_mean"]
