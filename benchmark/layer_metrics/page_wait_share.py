"""Engine steps of the window that ended with a request queued, a slot free
and no page for it (the engine's ``admission_page_waits_total``: a step
whose record reads ``admit_stopped_by`` "no_pages"), over the window's
engine steps.  Where it is well above zero PAGES bound the batch and not
slots.  Nothing where the engine keeps no such count."""


def value(ctx):
    c = ctx.window["counters"]
    waits, steps = c.get("admission_page_waits"), c.get("engine_steps")
    if waits is None or not steps:
        return None
    return 100.0 * waits / steps
