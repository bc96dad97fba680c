"""A fill's first tokens stay on the device: ``_activate_rows`` hands them
to their rows in one program, a record rides with the next dispatched
chunk, and the host folds them at that chunk's harvest
(``_fold_first_tokens``).  THIS file is the CPU gate that the deferral
changes nothing a client can see: the tokens, log-probabilities and
finish reasons are those of the path that fetches the first tokens at
once (what a request with ``handoff_to`` still takes), at every pipeline
depth, on a dense paged stack, a stateful one with late joins and a
window one.  A page boundary, a row that its first token ends, and
everything that drains the ring with a token on its way:
``test_first_tokens_settled.py`` (two files, because one test process
can hold only so many engines' programs: ``/proc/self/maps``)."""

import json
import pathlib

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.models import hybrid
from tests.engine import test_pipeline_depth as dense
from tests.engine import test_state_slots as slots
from tests.engine import test_window_pages as window
from tests.engine.test_window_pages import _prompts, _req

@pytest.fixture(scope="module")
def stacks():
    return {}


def _stack(stacks, name):
    """(cfg, params) of a small stack, built once a module."""
    if name not in stacks:
        if name == "dense":
            _, cfg, params = dense.make_engine("paged")
        elif name == "stateful":
            cfg = slots.make_cfg(
                moe_first_expert=slots.FIRST, moe_held_experts=slots.HELD
            )
            params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
        else:
            cfg = window.make_cfg()
            params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
        stacks[name] = (cfg, params)
    return stacks[name]


def _engine(stacks, name, at_once=False, **kw):
    model = _stack(stacks, name)
    if name == "dense":
        eng = dense.make_engine("paged", params=model[1], **kw)[0]
    elif name == "stateful":
        # 16 rows: two snapshot slots for kept fills
        eng = slots.make_engine(model, max_batch=16, kv_cache_len=96, **kw)
    else:
        eng = window.make_engine(model, **kw)
    if at_once:
        # the path a handed-off request takes: every distribution's
        # first tokens fetched before anything else is dispatched, as
        # every distribution's were before
        eng._first_tokens_at_once = lambda targets: True
    return eng


def _greedy(qid, prompt, n):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(prompt), input_ids=list(prompt),
        gconfig=GenerationHyperparameters(max_new_tokens=n, greedy=True),
    )


def _step_until(eng, cond, max_steps=300):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if cond():
                return
            eng.step()
    raise AssertionError("the engine never got there")


def _run(eng):
    _step_until(eng, lambda: not eng.has_work, 800)
    return eng.drain_results()


def _step_until_decoding(eng, n):
    """``n`` rows decode, and the host has seen the first token of each."""
    _step_until(
        eng, lambda: eng.n_decoding == n and not _on_their_way(eng)
    )


def _on_their_way(eng):
    return [
        i for i, r in enumerate(eng.rows)
        if r is not None and r.first_on_its_way
    ]


def _activated(eng):
    """Fresh targets that have been handed their first token so far."""
    return eng.first_tokens_deferred_total + eng.first_tokens_blocking_total


def _seen(results):
    """What a client sees of each request."""
    return {
        qid: (list(o.output_ids), list(o.output_logprobs), o.no_eos)
        for qid, o in results.items()
    }


def _serve(eng, name, depth):
    """Two prompts with siblings in time, a sibling that comes late (it
    joins a kept fill, or reuses the cached prefix) and a lone request:
    requests end on different chunks, and a row is activated while the
    ring holds chunks that predate it.  Prompts of the depth's own, so an
    engine that has served another depth has none of them cached."""
    req = _greedy if name == "dense" else _req
    eng.pipeline_depth = depth
    a, b, c = _prompts(5 + depth, 37, 21, 9)
    before = _activated(eng)
    for i, n in enumerate((14, 9, 22)):
        eng.submit(req(f"a{i}", a, n))
    eng.submit(req("b0", b, 11))
    _step_until(eng, lambda: _activated(eng) == before + 4)
    for _ in range(2):
        eng.step()
    eng.submit(req("a-late", a, 7))
    eng.submit(req("b1", b, 6))
    eng.submit(req("c0", c, 17))
    return _seen(_run(eng))


@pytest.fixture(scope="module")
def pairs(stacks):
    """Two engines a stack, one that fetches every first token at once and
    one that does not, for ALL the depths (an engine's decode program is
    its own, and a test process can hold only so many): both are given the
    same requests in the same order, so they stand in the same state
    before every case."""
    built = {}

    def get(name):
        if name not in built:
            built[name] = (
                _engine(stacks, name, at_once=True), _engine(stacks, name)
            )
        return built[name]

    return get


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("name", ["dense", "stateful", "window"])
def test_the_tokens_are_those_of_the_blocking_path(
    stacks, pairs, name, depth
):
    blocking, eng = pairs(name)
    waits = []
    phase = eng._phases.phase

    def spy(span, **counts):
        if span == "areal.engine.fill.first_token_wait":
            waits.append(counts["rows"])
        return phase(span, **counts)

    eng._phases.phase = spy
    deferred = eng.first_tokens_deferred_total
    try:
        got = stacks["served", name, depth] = _serve(eng, name, depth)
    finally:
        eng._phases.phase = phase
    want = _serve(blocking, name, depth)
    assert got.keys() == want.keys() and len(want) == 7
    for qid in want:
        assert got[qid] == want[qid], qid
    # nothing was fetched at once, every fresh target's token was picked
    # up at a fold, and each once; the other engine fetched every one
    assert eng.first_tokens_blocking_total == 0
    assert eng.first_tokens_deferred_total - deferred == 7 == sum(waits)
    assert not eng._first_tokens and not _on_their_way(eng)
    assert blocking.first_tokens_deferred_total == 0
    assert blocking.first_tokens_blocking_total == eng.first_tokens_deferred_total
    if name == "stateful":  # (a-late and b1 of every depth so far)
        assert eng.state_late_joins_total == blocking.state_late_joins_total
        assert eng.state_late_joins_total >= 2


@pytest.mark.parametrize("name", ["dense", "stateful", "window"])
def test_the_tokens_are_those_the_parent_commit_gave(stacks, pairs, name):
    """The blocking path above is this tree's too (the same activation
    program, fetched from at once).  ``first_tokens_parent.json`` holds
    what the commit BEFORE the change gave for ``_serve`` at depth 2: the
    host fetched every distribution's first tokens and scattered them to
    the rows itself."""
    got = stacks.get(("served", name, 2)) or _serve(pairs(name)[1], name, 2)
    recorded = json.loads(
        pathlib.Path(__file__).with_name("first_tokens_parent.json").read_text()
    )[name]
    assert got.keys() == recorded.keys()
    for qid, (ids, logps, no_eos) in recorded.items():
        assert got[qid][0] == ids and got[qid][2] == no_eos, qid
        np.testing.assert_allclose(got[qid][1], logps, atol=2e-5)
