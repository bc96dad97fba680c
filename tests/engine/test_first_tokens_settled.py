"""A fill's first tokens stay on the device until the harvest that brings
them (``test_first_tokens_on_device.py`` has the mechanism and the
token-exact comparison).  Here: a first token on a page boundary, a row
that its first token ends, everything that drains the ring with a token
on its way (pause, preemption, cancel, a weight swap), and the activation
program built before the first request."""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.engine import inference_server
from areal_tpu.models import transformer
from tests.engine import test_pipeline_depth as dense
from tests.engine.test_first_tokens_on_device import (  # noqa: F401
    _activated,
    _engine,
    _greedy,
    _on_their_way,
    _run,
    _seen,
    _stack,
    _step_until,
    _step_until_decoding,
    stacks,
)
from tests.engine.test_window_pages import _prompts, _req

PAGE = 16  # the dense engine's page


@pytest.fixture(scope="module")
def boundary_engines(stacks):
    """(deferred, blocking), for every case: the same requests in the same
    order, so the two stand in the same state before each."""
    return [
        _engine(stacks, "dense", at_once=flag, chunk_size=8, pipeline_depth=3)
        for flag in (False, True)
    ]


@pytest.mark.parametrize("plen", [PAGE - 1, PAGE, 2 * PAGE - 1, 2 * PAGE])
def test_a_first_token_on_a_page_boundary_writes_no_page_it_lacks(
    stacks, boundary_engines, plen
):
    """``len(prompt) % page_size`` in ``{page_size - 1, 0}``: the token on
    its way is among what ``_ensure_decode_blocks`` counts, so the pages a
    row holds cover every position the device has been asked to write,
    after every step; the pages are handed out as the blocking path hands
    them out, and the tokens are the reference's."""
    _, params = _stack(stacks, "dense")
    cfg = dense.make_engine("paged", params=params)[1]
    (p, other) = _prompts(plen, plen, 5)
    want = {
        f"edge{plen}": (p, 20), f"sibling{plen}": (p, 12),
        f"other{plen}": (other, 30),
    }
    allocated = []
    for eng in boundary_engines:
        before = _activated(eng), eng._pages.allocated_total
        eng.submit(_greedy(f"other{plen}", other, 30))
        _step_until(eng, lambda: _activated(eng) == before[0] + 1)
        for _ in range(3):  # (the same steps in both: ONE schedule)
            eng.step()
        eng.submit(_greedy(f"edge{plen}", p, 20))
        eng.submit(_greedy(f"sibling{plen}", p, 12))
        seen = False
        for _ in range(200):
            if not eng.has_work:
                break
            eng.step()
            seen = seen or bool(_on_their_way(eng))
            lengths = np.asarray(eng.kv_lengths)
            for rid, row in enumerate(eng.rows):
                if row is not None and not row.filling:
                    held = len(eng._pages.rows[rid]) * PAGE
                    assert lengths[rid] <= held, (rid, lengths[rid], held)
        assert seen != bool(eng.first_tokens_blocking_total)
        allocated.append(eng._pages.allocated_total - before[1])
        got = eng.drain_results()
        for qid, (prompt, n) in want.items():
            assert got[qid].output_ids == dense._ref_ids(
                params, cfg, prompt, n
            )["output_ids"], qid
    assert allocated[0] == allocated[1] > 0


def _pages_all_free(eng):
    while eng._kept.evict("pages") or eng._evict_parked() is not None:
        pass
    if eng._prefix_cache is not None:
        eng._prefix_cache.evict(eng.n_blocks)
    return all(p.free_blocks == p.n_blocks for p in eng._pools)


@pytest.mark.parametrize("why", ["stop_token", "budget_of_one"])
def test_a_row_that_its_first_token_ends(stacks, why):
    """The device stops such a row when it activates it; the host ends it
    with ONE token at the harvest that brings the token, and its slot and
    pages are free then.  The row beside it decodes as if alone."""
    _, params = _stack(stacks, "dense")
    cfg = dense.make_engine("paged", params=params)[1]
    long_p, p = [11, 12, 13], [7, 8, 9, 10]
    first = dense._ref_ids(params, cfg, p, 4)["output_ids"][0]
    assert first != dense.EOS
    kw = dict(stop_tokens=(dense.EOS, first)) if why == "stop_token" else {}
    budget = 9 if why == "stop_token" else 1
    eng = _engine(stacks, "dense", pipeline_depth=2, **kw)
    eng.submit(_greedy("long", long_p, 30))
    _step_until_decoding(eng, 1)
    eng.submit(_greedy("short", p, budget))
    _step_until(eng, lambda: _on_their_way(eng))
    (rid,) = _on_their_way(eng)
    chunks = eng.chunks_total
    # the record rides with the chunk dispatched in that step: its
    # harvest, the next step's, ends the row
    _step_until(eng, lambda: eng.rows[rid] is None)
    assert eng.chunks_total <= chunks + 1
    assert eng._pages.rows[rid] == []
    out = eng.wait_result("short", timeout=5)
    assert out.output_ids == [first] and len(out.output_logprobs) == 1
    assert out.no_eos == (why == "budget_of_one")
    ref = dense._ref_ids(params, cfg, long_p, 30)["output_ids"]
    if why == "stop_token" and first in ref:
        ref = ref[: ref.index(first) + 1]
    assert _run(eng)["long"].output_ids == ref
    assert eng.first_tokens_blocking_total == 0
    assert eng.first_tokens_deferred_total == 2
    assert _pages_all_free(eng)


def test_the_first_token_comes_before_its_chunk_is_waited_for(stacks):
    """The sample program is queued BEFORE the chunk that carries its
    record: that chunk's harvest waits for the tokens alone, folds them
    (the stream and the SLO's first-token stamp have them when the device
    has made them, as when the host fetched them at the fill's end), and
    only then waits for the chunk's own outputs."""
    eng, rid, _ = _with_a_token_on_its_way(stacks)
    row = eng.rows[rid]
    spans, phase = [], eng._phases.phase

    def spy(span, **counts):
        spans.append(span.removeprefix("areal.engine."))
        if spans[-3:] == ["fill.activate", "harvest.wait", "harvest.fetch"]:
            # (the carrying chunk's own outputs are not folded yet)
            seen["first"] = (list(row.generated), row.t_first)
        return phase(span, **counts)

    seen = {}
    eng._phases.phase = spy
    try:
        _step_until(eng, lambda: not _on_their_way(eng))
    finally:
        eng._phases.phase = phase
    at = spans.index("fill.first_token_wait")
    assert spans[at - 1 : at + 5] == [
        "harvest.wait", "fill.first_token_wait", "fill.activate",
        "harvest.wait", "harvest.fetch", "harvest.fold",
    ]
    # (depth 3: the chunks harvested before carried nothing)
    assert "fill.first_token_wait" not in spans[:at]
    generated, t_first = seen["first"]
    assert len(generated) == 1 and t_first > 0
    assert row.t_last >= row.t_first == t_first
    # (the run nothing disturbs, for the tests below: one engine fewer)
    stacks["undisturbed", "dense"] = _seen(_run(eng))


def test_a_lone_budget_of_one_ends_at_the_first_harvest(stacks):
    """Its budget is spent before its first dispatch: the one chunk that
    carries its record (an empty ring is always worth a dispatch) decodes
    nothing for it, and that chunk's harvest ends it."""
    eng = _engine(stacks, "dense")
    eng.submit(_greedy("one", [7, 8, 9], 1))
    out = _run(eng)["one"]
    assert len(out.output_ids) == 1 and out.no_eos
    assert eng.chunks_total == 1 and eng.first_tokens_deferred_total == 1
    assert all(r is None for r in eng.rows) and _pages_all_free(eng)


def _with_a_token_on_its_way(stacks, name="dense", **kw):
    """An engine with one row decoding and a second whose first token the
    host has not seen: (engine, that row's id)."""
    req = _greedy if name == "dense" else _req
    eng = _engine(stacks, name, pipeline_depth=3, **kw)
    long_p, p = _prompts(3, 19, 13)
    eng.submit(req("long", long_p, 40))
    _step_until_decoding(eng, 1)
    eng.submit(req("late", p, 24))
    _step_until(eng, lambda: _on_their_way(eng))
    (rid,) = _on_their_way(eng)
    assert eng.rows[rid].generated == [] and eng.inflight_chunks >= 1
    return eng, rid, (long_p, p)


@pytest.fixture(scope="module")
def undisturbed(stacks):
    def get(name):
        key = ("undisturbed", name)
        if key not in stacks:
            eng, _, _ = _with_a_token_on_its_way(stacks, name)
            stacks[key] = _seen(_run(eng))
        return stacks[key]

    return get


def test_a_pause_settles_the_token_and_loses_nothing(stacks, undisturbed):
    name = "dense"
    eng, rid, _ = _with_a_token_on_its_way(stacks, name)
    eng.pause()
    eng.step()
    row = eng.rows[rid]
    assert eng.inflight_chunks == 0 and not _on_their_way(eng)
    assert len(row.generated) >= 1 and row.cur_token == row.generated[-1]
    assert len(row.logprobs) == len(row.generated)
    eng.resume()
    assert _seen(_run(eng)) == undisturbed(name)


@pytest.mark.parametrize("name", ["dense", "stateful"])
def test_a_preemption_settles_the_token_and_loses_nothing(
    stacks, undisturbed, name
):
    """The victim is requeued WITH its first token, fills again over
    prompt + what it had and goes on where it was: the same tokens."""
    eng, rid, _ = _with_a_token_on_its_way(stacks, name)
    row = eng.rows[rid]
    built = inference_server._activate_rows._cache_size()
    eng._preempt_row(rid)
    assert eng.preempted_total == 1 and eng.rows[rid] is None
    assert not row.first_on_its_way and len(row.generated) >= 1
    assert row.cur_token == row.generated[-1]
    got, want = _seen(_run(eng)), undisturbed(name)
    for qid in want:
        assert got[qid][0] == want[qid][0] and got[qid][2] == want[qid][2]
        np.testing.assert_allclose(got[qid][1], want[qid][1], atol=2e-5)
    # the resumed row started again through a count built at the start
    assert inference_server._activate_rows._cache_size() == built


def test_a_cancel_settles_the_token_and_frees_the_row(stacks, undisturbed):
    eng, rid, _ = _with_a_token_on_its_way(stacks)
    assert eng.cancel("late")
    assert eng.rows[rid] is None and not _on_their_way(eng)
    assert eng._pages.rows[rid] == []
    got = _seen(_run(eng))
    assert got == {"long": undisturbed("dense")["long"]}
    assert _pages_all_free(eng)


def test_a_weight_swap_settles_the_token_first(stacks):
    """The token was sampled under the old weights and is the row's first;
    what follows the swap is computed under the new ones over prompt +
    everything the old ones gave, the token on its way among it."""
    eng, rid, (_, p) = _with_a_token_on_its_way(stacks)
    cfg, params = _stack(stacks, "dense")
    first = dense._ref_ids(params, cfg, p, 24)["output_ids"]
    params2 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    assert eng.update_weights(params2, version=1) == 2  # both in flight
    eng.step()
    row = eng.rows[rid]
    assert not _on_their_way(eng) and row is not None
    old = list(row.generated)
    # (the swap step may have folded a chunk under the new weights after)
    assert old[0] == first[0]
    out = _run(eng)["late"]
    assert out.version_start == 0 and out.version_end == 1
    got = list(out.output_ids)
    assert len(got) == 24 or got[-1] == dense.EOS
    # a prefix under the old weights (the first token at least), the rest
    # the new weights' greedy continuation of it
    for k in range(1, len(got) + 1):
        if got[:k] != first[:k]:
            break
        tail = dense.generate_tokens(
            params2, cfg, [p + got[:k]],
            GenerationHyperparameters(
                max_new_tokens=max(len(got) - k, 1), greedy=True
            ),
            dense.EOS, jax.random.PRNGKey(2),
        )[0]["output_ids"]
        if got[k:] == tail[: len(got) - k]:
            return
    raise AssertionError((got, first))


def test_the_activation_is_built_when_the_engine_starts(stacks):
    """Every padded count a distribution can have, before any request:
    serving then builds no activation program (a compile inside a
    benchmark's window makes the run incorrect)."""
    eng = _engine(stacks, "dense", max_batch=6)
    built = inference_server._activate_rows._cache_size()
    a, b = _prompts(7, 20, 9)
    for n in (1, 2, 3, 6):  # distributions of 1, 2, 3 (as 4) and 6 (as 8)
        for i in range(n):
            eng.submit(_greedy(f"n{n}-{i}", a if n > 1 else b, 5 + i))
        dense.run_until_done(eng)  # (in the context the engine was built in)
    assert len(eng.drain_results()) == 12
    assert inference_server._activate_rows._cache_size() == built

