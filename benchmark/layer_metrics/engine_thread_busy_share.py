"""The engine thread's time that is in no named wait, over the stretch:
the step records' self seconds of the phases ``engine_bookkeeping_share``
sums over a 3 s slice (``span_reduce.BOOKKEEPING``, the one list), over the
46 s around it (``lib/step_log.py``).  Not the host's own work alone: a
phase of the list that blocks behind the device keeps the block, and a
slice of a step or two rarely holds such a lap, so this reads above the
slice's number wherever they occur (the ``step_log`` line's
``engine_thread_busy_share_long_laps`` is their part)."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "engine", "engine_thread_busy_share")
