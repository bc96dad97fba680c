"""What the window layers' page rule returns to their pool: of the pages
the window layers' allocator handed out in the window, those that went
back to the free stack BEHIND a window (released by their last holder
because every holder's window had passed them: a fill as it went, a
decoding row as it grew, siblings one after the other), where without the
rule every page would live as long as a row that holds it.  The engine's
counters, the window's difference of each."""


def value(ctx):
    c = ctx.window["counters"]
    handed = c.get("window_pages_allocated")
    if not handed or handed <= 0:
        return None
    return 100.0 * c["window_pages_freed_behind"] / handed
