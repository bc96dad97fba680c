"""How unevenly the router loads the experts this chip holds, in the
trainer: over the window's steps, the (token, k) pairs of the busiest held
expert of each expert layer and micro-batch over the mean a held expert
took there (the trainer's record a batch, ``moe_busiest_pairs`` and
``moe_held_pairs``: summed on the device by the step program).  1.0 is an
even load; the grouped product's rounds follow the busiest expert
(``moe_extra_rounds``), so this is what its padding costs."""


def value(ctx):
    c = ctx.window["counters"]
    pairs, held = c.get("moe_held_pairs"), c.get("held_experts")
    if not pairs or not held:
        return None
    return c["moe_busiest_pairs"] * held / pairs
