"""Slots nobody asked for: over the stretch's engine steps that dispatched
a decode chunk, the step record's ``slots_empty`` + ``slots_parked`` over
``max_batch`` where ``admit_stopped_by`` reads ``queue_empty`` (the
traffic's: every queued request had been admitted, and a parked row gives
its slot to the first that needs one); ``lib/step_log.py``."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "engine", "slots_unrequested_share")
