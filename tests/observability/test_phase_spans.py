"""Phase spans (observability.tracing.phase): declared names, a no-op
outside a profiler session, and — under a profiler session on the CPU —
every engine span in the xplane, on one thread, nested inside an engine
step, with its counts readable."""

import glob
import os
import sys

import jax
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.observability.table import ENGINE_PHASES, TRACE_TABLE
from areal_tpu.observability.tracing import PhaseClock, phase

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _lint_module():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    return lint


def test_every_phase_literal_is_declared_and_every_declared_phase_used():
    lint = _lint_module()
    sites = lint.collect_phase_names()
    assert lint.phase_vocabulary_problems(sites, TRACE_TABLE) == []
    declared = {s.name for s in TRACE_TABLE if s.kind == "phase"}
    assert set(sites) == declared
    assert all(n.startswith("areal.") for n in declared)
    # the flight recorder's names stay apart from the phases' and the
    # device regions'
    assert not any(
        s.name.startswith("areal.")
        for s in TRACE_TABLE if s.kind not in ("phase", "region")
    )


@pytest.mark.parametrize(
    "source, expect",
    [
        ("with phase('areal.engine.nope'):\n    pass\n", "areal.engine.nope"),
        ("with clock.phase('areal.typo', n=1):\n    pass\n", "areal.typo"),
        ("with phase(name):\n    pass\n", "non-literal"),
    ],
)
def test_lint_fails_on_an_undeclared_phase(source, expect):
    lint = _lint_module()
    sites = lint.collect_phase_names(sources={"mod.py": source})
    problems = lint.phase_vocabulary_problems(sites, TRACE_TABLE)
    assert any(expect in p and "mod.py:1" in p for p in problems)


def test_phase_outside_a_session_records_nothing_and_raises_nothing():
    with phase("areal.engine.step", step=1, pages_live=2) as span:
        span.set_metadata(tokens=3)
    clock = PhaseClock(["a", "b"])
    with clock.phase("a", n=1):
        with clock.phase("b") as inner:
            inner.set_metadata(rows=2)
    # self seconds: the parent's time excludes the child's
    assert clock.seconds["a"] >= 0 and clock.seconds["b"] > 0
    with pytest.raises(ZeroDivisionError):
        with clock.phase("a"):
            1 / 0
    assert clock._open == []  # an exception closes the span too


def _req(qid, prompt, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=max_new, greedy=True),
    )


def _engine_events(trace_dir, prefix="areal.engine."):
    """{line id: [(start_ns, end_ns, name, {count: value})]} of the
    ``areal.engine.*`` events of the host plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats))
                for e in line.events
                if e.name.startswith(prefix)
            ]
            if evs:
                out[i] = evs
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_engine_spans_in_a_cpu_profile(tmp_path):
    """A tiny paged engine serving two groups, with a weight swap in the
    middle, under ``jax.profiler.start_trace``."""
    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=8, kv_cache_len=128, chunk_size=8,
        sampling=SamplingParams(greedy=True), stop_tokens=(),
        cache_mode="paged", page_size=16, prefill_chunk_tokens=32,
    )
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for g, plen in enumerate((20, 37)):
            prompt = [6 + (g + i) % 50 for i in range(plen)]
            for i in range(3):
                eng.submit(_req(f"g{g}-{i}", prompt, 12 + 8 * i))
        for k in range(400):
            if not eng.has_work:
                break
            if k == 3:
                eng.update_weights(params, version=1)
            eng.step()
        assert not eng.has_work
    finally:
        jax.profiler.stop_trace()

    lines = _engine_events(str(tmp_path))
    assert len(lines) == 1, "the engine's spans are on ONE thread"
    (events,) = lines.values()
    names = {e[2] for e in events}
    assert names == set(ENGINE_PHASES)
    steps = [e for e in events if e[2] == "areal.engine.step"]
    for s, e, name, _ in events:
        if name != "areal.engine.step":
            assert any(s0 <= s and e <= e0 for s0, e0, _, _ in steps), name

    def counts(name):
        return [c for _, _, n, c in events if n == name]

    last = counts("areal.engine.step")[-1]
    assert set(last) == {
        "step", "rows_decoding", "rows_filling", "pending", "ring",
        "tokens_emitted_total",
    }
    assert last["step"] == eng._step_seq
    assert last["tokens_emitted_total"] == eng.tokens_emitted_total
    assert sum(c["rows_admitted"] for c in counts("areal.engine.admit")) == 6
    fills = counts("areal.engine.fill.dispatch")
    # the counts of ITS event; the running totals a fill moves are the
    # engine's attributes (all six first tokens reached their rows on the
    # device, none was fetched at once)
    assert all(set(c) == {"prompts", "f_pad", "c", "tokens"} for c in fills)
    records = eng._phases.records()
    assert eng.first_tokens_deferred_total == 6
    assert eng.first_tokens_blocking_total == 0
    # the step span's counts are the record's numbers
    assert [r["step"] for r in records] == [
        c["step"] for c in counts("areal.engine.step")
    ]
    for r, c in zip(records, counts("areal.engine.step")):
        assert (r["slots_decoding"], r["slots_filling"], r["pending"],
                r["ring"]) == (c["rows_decoding"], c["rows_filling"],
                               c["pending"], c["ring"])
    assert sum(r["fill_programs"] for r in records) == len(fills)
    assert sum(r["fill_tokens"] for r in records) == eng.prefill_tokens_total
    assert sum(r["fill_slots"] for r in records) == sum(
        c["f_pad"] * c["c"] for c in fills
    )
    # two unique prompts prefilled once each, and once more for the rows
    # the swap recomputed
    assert sum(c["tokens"] for c in fills) == eng.prefill_tokens_total
    assert sum(
        c["rows"] for c in counts("areal.engine.fill.first_token_wait")
    ) == 6
    (swap,) = counts("areal.engine.swap")
    assert swap["version"] == 1 and swap["rows_recomputed"] > 0
    disp = counts("areal.engine.decode.dispatch")
    assert all(
        c["chunk_size"] == 8 and c["rows"] == c["rows_planned"] > 0
        and c["ctx_tokens_sum"] >= 20 * c["rows"]
        and c["pages_attended"] >= c["rows"]
        and c["page_slots"] >= c["pages_attended"]
        for c in disp
    )
    folded = sum(c["tokens"] for c in counts("areal.engine.harvest.fold"))
    assert folded + 6 == eng.tokens_emitted_total
    assert all(
        set(c) == {
            "blocks_allocated", "rows_preempted", "pages_live", "pages_total"
        }
        for c in counts("areal.engine.ensure_blocks")
    )
    # the one span that carries the pool's state
    ensured = counts("areal.engine.ensure_blocks")
    assert all(c["pages_total"] == eng.n_blocks for c in ensured)
    assert max(c["pages_live"] for c in ensured) > 0
    # each phase says where it begins and where it has ended, in two spans
    # of no length around it on the same thread, nested as the phases are
    (marks,) = _engine_events(str(tmp_path), prefix="areal.phase.").values()
    assert len(marks) == 2 * len(events)
    open_, closed = [], []
    for m in sorted(marks):
        if m[2] == "areal.phase.begin":
            assert m[3].keys() == {"of", "t", "seq"}
            open_.append(m)
        else:
            begin = open_.pop()
            assert m[2] == "areal.phase.end" and m[3]["of"] == begin[3]["of"]
            closed.append((begin, m))
    assert not open_
    longer = []
    for (s, e, name, _), (begin, end) in zip(
        sorted(events), sorted(closed)
    ):
        assert begin[3]["of"] == name and begin[1] <= s and e <= end[0]
        longer.append((e - s) * 1e-9 - end[3]["seconds"])
    # ... and how long it took.  The clock's two readings lie INSIDE the
    # span's, a few instructions from them (1-15 us on a quiet machine), so
    # the span is never the shorter; it is the longer by what the machine
    # took the thread away for in between, which is nothing in most spans
    # and was 0.5 ms in one span of the driver's six-worker run and 0.4 and
    # 2.8 ms in one of fifty with the machine three times oversubscribed
    assert min(longer) > -2e-4
    assert sorted(longer)[len(longer) * 9 // 10] < 2e-4


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_routing_decision_is_one_span_of_the_managers_thread(tmp_path):
    """``areal.manager.schedule`` around each decision, and nothing for a
    poll: the manager polls without a pause."""
    from tests.system.test_gserver_manager_unit import _manager

    m = _manager(policy="least_token_usage")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(5):
            m._schedule_request(f"g{i}-0", prompt_len=100, new_token_budget=20)
    finally:
        jax.profiler.stop_trace()
    (events,) = _engine_events(str(tmp_path), prefix="areal.").values()
    assert [e[2] for e in events] == ["areal.manager.schedule"] * 5
