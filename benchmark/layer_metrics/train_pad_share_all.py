"""Padding in the trainer's device layout over EVERY minibatch of the
traced slice: ``1 - sum(real_tokens) / sum(padded_slots)`` of the
``areal.train.batch`` spans' counts (``train_pad_share`` reads the engine's
figure for each step's last minibatch only)."""

from benchmark.lib import span_reduce


def value(ctx):
    t = span_reduce.spans_of(ctx)
    batches = [
        s for s in (span_reduce.named(t, span_reduce.BATCH) if t else [])
        if s.counts.get("padded_slots", 0) > 0
    ]
    if not batches:
        return None
    real = sum(s.counts["real_tokens"] for s in batches)
    return 100.0 * (1.0 - real / sum(s.counts["padded_slots"] for s in batches))
