"""The greedy-divergence statistic the quantized-serving tests share."""


def lcp_divergence(ref_streams, got_streams):
    """Greedy divergence between two {qid: tokens} stream maps:
    ``1 - (longest-common-prefix tokens / reference tokens)`` — one
    early flip charges the whole tail (the conservative definition).
    Returns ``(rate, diverged_request_count)``."""
    total = matched = diverged = 0
    for qid, ref in ref_streams.items():
        got = got_streams[qid]
        lcp = 0
        for a, b in zip(ref, got):
            if a != b:
                break
            lcp += 1
        total += len(ref)
        matched += lcp
        diverged += int(lcp < max(len(ref), len(got)))
    return round(1.0 - matched / max(total, 1), 4), diverged
