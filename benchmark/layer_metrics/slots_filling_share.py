"""Slots a decode chunk computed for nothing because their prompt was
still prefilling: over the stretch's engine steps that dispatched a decode
chunk, the step record's ``slots_filling`` over ``max_batch``
(``lib/step_log.py``: the program's record a step, cut to the 46 s around
the traced slice).  With ``slots_unrequested_share``,
``slots_blocked_share`` and ``decode_rows_mean`` / ``max_batch`` it says
where a chunk's slots went."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "engine", "slots_filling_share")
