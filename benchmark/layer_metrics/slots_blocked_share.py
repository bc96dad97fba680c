"""Slots a queued request could not take: over the stretch's engine steps
that dispatched a decode chunk, the step record's ``slots_empty`` +
``slots_parked`` over ``max_batch`` where ``admit_stopped_by`` names any
reason but ``queue_empty`` (no slot: the parked rows' own continuations
are queued; no pages, held, the late-join cap, a prefix pull);
``lib/step_log.py``."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "engine", "slots_blocked_share")
