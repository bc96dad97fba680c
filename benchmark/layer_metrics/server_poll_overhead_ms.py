"""What a poll of the generation server spends outside the engine's step:
the sum of the mean lengths of its four parts in the traced slice,
``areal.gserver.serve_api``, ``.apply_commands``, ``.reply`` and
``.export_metrics`` (a poll less its ``areal.engine.step`` child, but for
the microsecond between the parts; taken from the parts because they are
short and so survive the slice's edges, which a poll of over a second
often does not)."""

from benchmark.lib import span_reduce


def value(ctx):
    return span_reduce.sum_of_mean_ms(ctx, span_reduce.POLL_PARTS)
