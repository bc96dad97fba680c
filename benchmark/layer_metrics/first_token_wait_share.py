"""The engine thread's time blocked fetching a completed prefill's first
sampled tokens (``areal.engine.fill.first_token_wait``: the fetch waits
for the fill program behind every decode chunk already queued), over the
traced slice (from the first to the last thing the trace saw).  A fetch
that an edge of the slice cut counts for the part inside it, as the
engine's ``areal.phase.begin`` / ``areal.phase.end`` marks give it
(``span_reduce.with_cut_phases``).  A fetch lasts a second or more and the
slice three: the share says what THIS slice held, and swings with where it
fell; the engine's ``phase_seconds()`` has the whole run."""

from benchmark.lib import span_reduce


def value(ctx):
    return span_reduce.share_of_engine_thread(ctx, [span_reduce.FIRST_TOKEN_WAIT])
