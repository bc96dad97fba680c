"""What the interface, the model worker and the caller did with the
device waiting: mean over the stretch of a batch record's ``t0`` less the
record before's ``t1`` (the trainer's ``PhaseClock``, a record a
``train_batch``; ``lib/step_log.py``)."""

from benchmark.lib import step_log


def value(ctx):
    return step_log.metric(ctx, "train", "between_batches_ms")
