"""How much of the routed work a group-limited router sends to THIS chip,
against an even split: over the window's decode chunks, the (token, k)
pairs the chip's held experts took over all pairs routed, times the chips
that share a layer (router outputs / held experts).  100 is an even share.
It says what the group limit does to one chip's load: a token's pairs
fall in 4 of 8 groups, so a chip's share comes in bursts of a whole
token's pairs in its group or none.  (The window record and the
``window_closed`` line carry ``moe_groups_hit`` beside it: the (token,
chosen group) pairs whose group has an expert of this chip.)"""


def value(ctx):
    c = ctx.window["counters"]
    routed, held = c.get("moe_pairs_routed"), c.get("moe_pairs_held")
    if not routed or held is None or "latent_shape" not in c:
        return None
    _, _, n_outputs, n_held, _ = c["latent_shape"]
    return 100.0 * held / routed * (n_outputs / n_held)
