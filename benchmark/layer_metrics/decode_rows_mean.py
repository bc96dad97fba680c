"""How full the decode batch really ran: tokens generated in the window
(the benchmark's count at ``engine.step()``) over the window's decode steps
(``chunks`` of ``timing_split()`` times the chunk size)."""


def value(ctx):
    c = ctx.window["counters"]
    steps = c["decode_chunks"] * c["chunk_size"]
    return c["tokens_emitted"] / steps if steps > 0 else None
