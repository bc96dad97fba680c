"""How unevenly the router loads the experts this chip holds: over the
window's decode chunks, the (token, k) pairs of the busiest held expert
over the mean a held expert took (the engine's ``[held]`` histogram,
summed on the device by the decode program and fetched with each chunk's
tokens; the window's difference of it).  1.0 is an even load; the expert
products are bound by their weights' bytes at these counts, so this says
how far the cell is from a deployment's skew, not what the skew costs."""


def value(ctx):
    pairs = ctx.window["counters"].get("moe_expert_pairs")
    if not pairs or sum(pairs) <= 0:
        return None
    return max(pairs) / (sum(pairs) / len(pairs))
