"""``lib/span_reduce.py`` and the seven readers on top of it, on a small
trace recorded on one TPU v5 lite (PR 24).

``data/small_spans_v5e.xplane.pb`` is a hand-made scenario: the program's
span hierarchy (``observability.tracing.phase`` and ``PhaseClock`` with the
names and counts of ``docs/observability.md``, "Phase spans") made by hand
around three tiny jitted programs.  Thread 1 makes five
``areal.gserver.poll`` spans, each with the server's four parts and an
``areal.engine.step`` whose dispatch span starts one
``jit_paged_decode_chunk`` (one call of the paged kernel with one query
row a sequence: the Mosaic call ``paged_attn_decode``); polls 1 and 3 also
run a fill (an elementwise stand-in named ``jit_paged_fill_chunk``) and
fetch its result in ``areal.engine.fill.first_token_wait``, between two
``areal.engine.fill.activate`` spans.  The profiler's session STARTS
inside the first poll's fetch and STOPS inside the fifth poll's
``areal.engine.harvest.wait``: those two waits, and the steps and polls
around them, are not in the file; the engine phases' marks
(``areal.phase.begin``, ``areal.phase.end``) and the other children are.
Before the fifth poll the same thread makes one ``areal.train.step`` of two
``areal.train.batch`` spans around ``jit_train_step`` (a matmul and a
tanh).  Thread 2 makes an ``areal.manager.schedule`` span every 4 ms to the
session's end.  One decode program was dispatched before the first poll
(the ring's head start).

Every number below was read off the raw events by hand (start and
duration in ns, as ``ProfileData`` lists them), the way
``test_trace_reduction_of_the_recorded_v5e_trace`` does.
"""

import json
import os
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import span_reduce as sr  # noqa: E402

NS = 1e-9
#: the first thing the trace saw, a manager's span, and the last, the end
#: of its seventeenth (117,420,002 + 1,173,010)
T0_NS, T1_NS = 48_110_407, 118_593_012
SLICE_NS = T1_NS - T0_NS  # 70,482,605
#: open when the session started: the fetch, whose ``areal.phase.end`` is
#: at 50,824,827 and says it lasted 51.8 ms, longer than the trace, and
#: the step around it, whose mark is at 56,481,687
CUT_FETCH_NS = 50_824_827 - T0_NS  # 2,714,420
CUT_STEP_1_NS = 56_481_687 - T0_NS  # 8,371,280
#: open when it stopped: the step whose ``areal.phase.begin`` is at
#: 110,614,703 and 1,340 long, and its harvest wait (113,331,512, 680)
CUT_STEP_5_NS = T1_NS - 110_616_043  # 7,976,969
CUT_HARVEST_NS = T1_NS - 113_332_192  # 5,260,820

NEW_READERS = [
    "engine_bookkeeping_share", "first_token_wait_share",
    "server_poll_overhead_ms", "kv_pages_live_share",
    "paged_attn_hbm_share", "train_pad_share_all", "train_host_prep_ms",
]


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "span_reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def ctx(tmp_path):
    """A context as ``run.py`` leaves it after a traced run: the xplane
    under ``<out>/trace``, the work directory beside it."""
    prof = tmp_path / "trace" / "plugins" / "profile" / "2026_09_27_15_26_51"
    prof.mkdir(parents=True)
    shutil.copy(
        os.path.join(DATA, "small_spans_v5e.xplane.pb"),
        prof / "small.xplane.pb",
    )
    (tmp_path / "work").mkdir()
    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        config = json.load(f)
    return types.SimpleNamespace(
        work_dir=str(tmp_path / "work"), config=config,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    )


@pytest.fixture()
def trace(ctx):
    return sr.load(sr.xplane_of(ctx))


def test_the_recorded_file_is_small():
    assert os.path.getsize(os.path.join(DATA, "small_spans_v5e.xplane.pb")) < 100_000


def test_spans_are_kept_per_line_with_their_counts(trace):
    engine_line, manager_line = sorted(trace["lines"], key=len, reverse=True)
    # 136 events less the 72 marks, plus the four cut phases
    assert len(engine_line) == 68 and len(manager_line) == 17
    assert {s.name for s in manager_line} == {"areal.manager.schedule"}
    assert not any(s.name.startswith("areal.phase.") for s in engine_line)
    steps = [s for s in engine_line if s.name == sr.STEP]
    # the first and the fifth step come back from their marks, without counts
    assert [s.counts.get("step") for s in steps] == [None, 2, 3, 4, None]
    assert steps[2].counts == {
        "step": 3, "rows_decoding": 4, "rows_filling": 0, "pending": 0,
        "ring": 1, "tokens_emitted_total": 14,
    }
    assert steps[2].end - steps[2].start == pytest.approx(7_026_010 * NS)
    (fill,) = sr.named(trace, "areal.engine.fill.dispatch")
    assert fill.counts == {"prompts": 2, "f_pad": 2, "c": 32, "tokens": 45}
    assert sr.line_of(trace, sr.POLL) is engine_line
    assert sr.line_of(trace, "areal.no.such") == []
    # 42 operations on the chip's ``XLA Ops`` line
    assert {k: len(v) for k, v in trace["devices"].items()} == {
        "/device:TPU:0": 42
    }
    t0, t1 = sr.extent(trace)
    assert (t0, t1) == (pytest.approx(T0_NS * NS), pytest.approx(T1_NS * NS))


def test_a_phase_that_an_edge_cut_comes_back_from_its_marks(trace):
    """A span that is open when the profiler starts or stops is not
    recorded; the marks of no length around an engine phase are."""
    fetch, first, last, harvest = trace["cut_phases"]
    assert [c.name for c in trace["cut_phases"]] == [
        sr.FIRST_TOKEN_WAIT, sr.STEP, sr.STEP, "areal.engine.harvest.wait"
    ]
    assert fetch.start == first.start == pytest.approx(T0_NS * NS)
    assert fetch.end - fetch.start == pytest.approx(CUT_FETCH_NS * NS)
    assert first.end - first.start == pytest.approx(CUT_STEP_1_NS * NS)
    assert harvest.end == last.end == pytest.approx(T1_NS * NS)
    assert last.end - last.start == pytest.approx(CUT_STEP_5_NS * NS)
    assert harvest.end - harvest.start == pytest.approx(CUT_HARVEST_NS * NS)
    line = sr.line_of(trace, sr.POLL)
    assert all(c in line for c in trace["cut_phases"])
    # four harvest waits and one fetch were recorded whole, with both marks
    assert len([s for s in line if s.name == harvest.name]) == 5
    assert len([s for s in line if s.name == fetch.name]) == 2


def test_cut_phases_from_marks_alone():
    def mark(t, name, **counts):
        return sr.Span(t, t + 1e-6, name, counts)

    step, wait = sr.STEP, "areal.engine.harvest.wait"
    work = sr.Span(5.0, 5.5, "areal.engine.admit", {})
    whole = sr.Span(6.0, 7.0, wait, {})
    spans = [
        mark(2.0, sr.PHASE_END, of=wait, seconds=0.5),
        mark(2.5, sr.PHASE_END, of=step, seconds=30.0),
        mark(4.0, sr.PHASE_BEGIN, of=step),
        mark(4.9, sr.PHASE_BEGIN, of="areal.engine.admit"),
        work,
        mark(5.6, sr.PHASE_END, of="areal.engine.admit", seconds=0.5),
        mark(5.9, sr.PHASE_BEGIN, of=wait),
        whole,
        mark(7.1, sr.PHASE_END, of=wait, seconds=1.0),
        mark(8.0, sr.PHASE_BEGIN, of=sr.FIRST_TOKEN_WAIT),
    ]
    kept, cuts = sr.with_cut_phases(spans, 1.0, 10.0)
    # the first wait says it lasted 0.5 s: it began inside the trace, before
    # the host's recorder did, and is not stretched to the trace's start;
    # the step around it lasted longer than the trace
    assert cuts[0] == sr.Span(1.5, 2.0, wait, {})
    assert cuts[1] == sr.Span(1.0, 2.5, step, {})
    # open at the end, outermost first: the second step and its fetch
    assert cuts[2][:3] == (4.0 + 1e-6, 10.0, step)
    assert cuts[3][:3] == (8.0 + 1e-6, 10.0, sr.FIRST_TOKEN_WAIT)
    assert kept == sorted([work, whole] + cuts, key=lambda s: s[:3])
    # a mark that does not say how long: from the trace's start
    kept, cuts = sr.with_cut_phases(
        [mark(2.0, sr.PHASE_END, of=wait)], 1.0, 10.0
    )
    assert cuts == [sr.Span(1.0, 2.0, wait, {})]
    # no marks (a program from before them, a thread without a PhaseClock):
    # nothing is put back
    assert sr.with_cut_phases([work, whole], 1.0, 10.0) == ([work, whole], [])


def test_self_time_subtracts_the_children(trace):
    base, by_name = sr.engine_thread(trace)
    assert base == pytest.approx(SLICE_NS * NS, rel=1e-9)
    # every instant that a span of the thread covers belongs to exactly one
    # span; the rest of the slice is the own time of the cut polls, the
    # worker's loop between polls, and here the train step
    line = [s for s in sr.line_of(trace, sr.POLL) if s.name.startswith(sr.SERVER)]
    covered, _ = sr.union_seconds([s[:3] for s in line])
    assert sum(by_name.values()) == pytest.approx(covered, rel=1e-9)
    assert covered < base - 9_789_120 * NS
    # step 3: 7,026,010 less admit 1,192,550, fill.dispatch 268,800,
    # fill.activate 272,350 and 743,330, the fetch 873,270, ensure_blocks
    # 1,175,920, decode.dispatch 274,000, harvest wait 10,780, fetch 476,870,
    # fold 497,730 = 1,240,410; steps 2 and 4 likewise 1,189,190 and
    # 1,270,410; of the first step's 8,371,280 inside the trace its seven
    # children take 6,912,699, of the fifth's 7,976,969 its four 7,920,890
    assert by_name[sr.STEP] == pytest.approx(
        (1_458_581 + 1_189_190 + 1_240_410 + 1_270_410 + 56_079) * NS, rel=1e-6
    )
    assert by_name["areal.engine.fill.activate"] == pytest.approx(
        (850_130 + 272_350 + 743_330) * NS, rel=1e-6
    )
    # the fetch recorded whole, and the part of the cut one inside the trace
    assert by_name[sr.FIRST_TOKEN_WAIT] == pytest.approx(
        (873_270 + CUT_FETCH_NS) * NS, rel=1e-6
    )
    assert by_name["areal.engine.harvest.wait"] == pytest.approx(
        (19_970 + 8_900 + 10_780 + 10_830 + CUT_HARVEST_NS) * NS, rel=1e-6
    )
    # poll 2 outside its five children: 10,414,170 - 10,358,142
    polls = [s for s in sr.line_of(trace, sr.POLL) if s.name == sr.POLL]
    first = sr.self_seconds(sr.inside(sr.line_of(trace, sr.POLL), polls[:1]))
    assert first[sr.POLL] == pytest.approx(56_028 * NS, rel=1e-4)
    # the train spans are on the same thread here, and not the server's
    assert not any(n.startswith("areal.train.") for n in by_name)


def test_the_split_survives_a_slice_that_cuts_every_poll(trace):
    """Without the polls and the steps (a program whose steps leave no
    marks) the split is taken over what is left, as roots, and over the
    same slice."""
    cut = {
        "devices": trace["devices"],
        "lines": [
            [s for s in ln if s.name not in (sr.POLL, sr.STEP)]
            for ln in trace["lines"]
        ],
    }
    whole_base, whole = sr.engine_thread(trace)
    base, by_name = sr.engine_thread(cut)
    assert base == whole_base
    assert sr.STEP not in by_name and sr.POLL not in by_name
    # the polls' own 56,028 + 39,679 + 47,680 and the steps' 5,214,670
    assert sum(whole.values()) - sum(by_name.values()) == pytest.approx(
        (143_387 + 5_214_670) * NS, rel=1e-6
    )
    assert by_name[sr.FIRST_TOKEN_WAIT] == whole[sr.FIRST_TOKEN_WAIT]


def test_idle_gaps_are_named_by_the_innermost_span_of_the_feeding_thread(trace):
    got = sr.idle_by_span(trace)
    # 7 between the 8 programs, 1 before the first and 1 after the last,
    # and 26 of 1-3 ns between operations of one program
    assert got["gaps"] == 35
    by_hand = [
        # after poll 4's last span, before the train step: nobody's
        ("none", 13_596_057),
        # program 1 ended in poll 1's dispatch, program 2 came in poll 2
        ("areal.gserver.export_metrics", 11_091_250),
        ("areal.gserver.export_metrics", 10_788_675),
        ("areal.gserver.reply", 10_333_572),
        # from the last operation to the trace's end: the cut harvest wait,
        # inside the cut step
        ("areal.engine.harvest.wait", 6_439_800),
        ("areal.gserver.serve_api", 5_870_870),
        # from the trace's start to the first operation: the cut fetch
        ("areal.engine.fill.first_token_wait", 4_535_269),
        ("areal.train.pack", 4_344_594),
        # the fill ran 827 ns; the fetch of its result covers the middle
        ("areal.engine.fill.first_token_wait", 3_443_530),
    ]
    assert [o for o, _ in got["longest"][:9]] == [o for o, _ in by_hand]
    for (_, sec), (_, ns) in zip(got["longest"], by_hand):
        assert sec == pytest.approx(ns * NS, rel=1e-6)
    assert len(got["longest"]) == 10 and got["longest"][9][1] < 5e-9
    assert got["idle_s"]["none"] == pytest.approx(13_596_057 * NS, rel=1e-3)
    # the trace spans 70,482,605 ns and the 42 operations cover 38,951
    assert sum(got["idle_s"].values()) == pytest.approx(
        (SLICE_NS - 38_951) * NS, rel=1e-6
    )
    # the manager's thread feeds no device: where only it has a span the
    # gap is nobody's, unless the trace shows no feeding thread at all
    assert sr.gap_owner(trace, 96_900_000 * NS, 97_000_000 * NS) == "none"
    manager_only = dict(trace, lines=[min(trace["lines"], key=len)])
    assert sr.gap_owner(manager_only, 96_900_000 * NS, 97_000_000 * NS) == (
        "areal.manager.schedule"
    )


def test_the_new_readers_against_the_raw_events(ctx, capsys):
    got = {name: _reader(name).value(ctx) for name in NEW_READERS}
    # step self 5,214,670 + admit 4,688,920 + fill.dispatch 268,800 +
    # fill.activate 1,865,810 + ensure_blocks 5,940,000 + decode.dispatch
    # 2,130,780 + harvest.fold 3,536,070 = 23,645,050
    assert got["engine_bookkeeping_share"] == pytest.approx(
        100 * 23_645_050 / SLICE_NS, rel=1e-6
    )
    assert got["first_token_wait_share"] == pytest.approx(
        100 * (873_270 + CUT_FETCH_NS) / SLICE_NS, rel=1e-6
    )
    # the four parts' means over four polls each: serve_api 4,640,009,
    # apply_commands 4,712,330, reply 4,739,080, export_metrics 4,590,322
    assert got["server_poll_overhead_ms"] == pytest.approx(
        18_681_741 / 4 * 1e-6, rel=1e-6
    )
    # the five ``ensure_blocks`` spans: 4, 5, 7, 6 and 6 pages of 16
    assert got["kv_pages_live_share"] == pytest.approx(100 * 28 / 80)
    # 5 executions of paged_attn_decode.1 took 6,338 + 6,141 + 6,148 +
    # 6,143 + 6,151 ns; the five dispatch spans carry 500, 520, 540, 556
    # and 572 attended positions, each 1,024 bytes in one layer (2 kv heads
    # x 128 x K and V x bf16): 5 x 537.6 x 1,024 bytes at 819 GB/s
    least = 5 * 537.6 * 1024 / 819e9
    assert got["paged_attn_hbm_share"] == pytest.approx(
        100 * least / (30_921 * NS), rel=1e-6
    )
    assert got["paged_attn_hbm_share"] == pytest.approx(10.87, abs=0.01)
    assert got["train_pad_share_all"] == pytest.approx(
        100 * (1 - (900 + 834) / (2048 + 1024))
    )
    # pack + upload: 2,313,940 + 1,157,350 and 2,398,290 + 1,121,110
    assert got["train_host_prep_ms"] == pytest.approx(3.495_345, rel=1e-6)
    # the first reader that ran printed the one idle_by_span line
    lines = [
        json.loads(ln) for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{")
    ]
    assert [ln["event"] for ln in lines] == ["idle_by_span"]
    assert lines[0]["longest"][0][0] == "none"
    assert lines[0]["engine_thread_s"] == pytest.approx(SLICE_NS * NS)
    assert lines[0]["cut_phases"] == [
        [sr.FIRST_TOKEN_WAIT, pytest.approx(CUT_FETCH_NS * NS)],
        [sr.STEP, pytest.approx(CUT_STEP_1_NS * NS)],
        [sr.STEP, pytest.approx(CUT_STEP_5_NS * NS)],
        ["areal.engine.harvest.wait", pytest.approx(CUT_HARVEST_NS * NS)],
    ]


def test_kernel_calls_by_name(trace):
    assert sr.kernel_calls(trace, "paged_attn_decode") == (
        5, pytest.approx(30_921 * NS), 1
    )
    assert sr.kernel_calls(trace, "paged_attn_fill") == (0, 0.0, 1)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_without_a_trace_or_without_spans(name, tmp_path):
    """An empty context (the made-up-run test of test_benchmark.py), a run
    without ``--trace 1``, and a program from before the spans (the PR 23
    trace: device operations and ``bench.`` annotations only)."""
    reader = _reader(name)
    assert reader.value(types.SimpleNamespace()) is None
    (tmp_path / "work").mkdir()
    untraced = types.SimpleNamespace(work_dir=str(tmp_path / "work"))
    assert reader.value(untraced) is None
    prof = tmp_path / "trace" / "plugins" / "profile" / "x"
    prof.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "small_v5e.xplane.pb"), prof / "p.xplane.pb")
    assert reader.value(untraced) is None
