"""The rare stall: the seconds of the stretch spent in laps of the
trainer's clock (a batch's begin to the next one's) longer than twice the
stretch's median lap, over the stretch.  0 in a plain window; the
``step_log`` line's ``stalled`` names each such lap and the phase that
held it (``lib/step_log.py``)."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "train", "step_stall_share")
