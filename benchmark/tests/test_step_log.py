"""``lib/step_log.py`` and the six readers over it: known answers on a
made-up log and capture, a lap the stretch's edge cuts, ``None`` wherever
there is nothing to read, and the command line on a toy run's file."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import step_log  # noqa: E402

ENGINE_READERS = {
    "slots_filling_share": 12.5,
    "slots_unrequested_share": 25.0,
    "slots_blocked_share": 12.5,
    "engine_thread_busy_share": 10.0,
}
TRAIN_READERS = {"train_between_batches_ms": 10.0, "train_step_stall_share": 5.0}


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine_records():
    """Thirty steps of 1 s from t=100, one chunk each but every fifth;
    of 8 slots 4 decode, 1 fills, and 3 hold no live row (two empty and
    one parked, or all three empty): nobody asked for them in the steps
    whose queue was empty, and in every fourth step a request stood
    queued for want of pages."""
    out = []
    for i in range(30):
        blocked = i % 4 == 3
        out.append({
            "seq": i + 1, "step": i + 1, "t0": 100.0 + i, "t1": 101.0 + i,
            "self_s": {
                "areal.engine.step": 0.04, "areal.engine.admit": 0.06,
                "areal.engine.harvest.wait": 0.85,
                "areal.engine.harvest.fetch": 0.05,
            },
            "compiles": int(i == 12), "compile_s": 0.5 * (i == 12),
            "slots_decoding": 4, "slots_filling": 1,
            "slots_parked": i % 2,
            "slots_empty": 3 - i % 2,
            "admit_stopped_by": "no_pages" if blocked else "queue_empty",
            "pending": int(blocked), "ring": 1, "chunk_size": 64,
            "version": 0, "tokens_emitted": 256,
            "rows_admitted": 0, "rows_finished": 0, "rows_preempted": 0,
            "decode_chunks": int(i % 5 != 4), "decode_rows": 4 * (i % 5 != 4),
            "fill_programs": 1, "fill_tokens": 100, "fill_slots": 128,
            "late_joins": 0,
        })
    return {"log": "engine", "lap": "areal.engine.step", "max_batch": 8,
            "dropped": 0, "phases": [], "laps": 30}, out


def test_the_engines_account_on_a_made_up_log():
    header, records = _engine_records()
    # the stretch cuts step 6 (t 105-106) in half and ends with step 25
    # (the capture: four decode executions of 0.5 s, the device busy 2.4
    # of the slice's 3.0 s)
    got = step_log.engine_account(
        header, records, 105.5, 125.0, (4, 2.0), {"busy_s": 2.4, "window_s": 3.0}
    )
    assert got["laps"] == 20 and got["stretch_s"] == 19.5
    assert got["lap_s_covered"] == pytest.approx(19.5)
    # steps 6..25 by number, i = 5..24: chunks in all but i = 9, 14, 19, 24,
    # and half of step i = 5's
    assert got["decode_chunks"] == pytest.approx(15.5)
    assert got["slots_decoding_share"] == pytest.approx(50.0)
    assert got["slots_filling_share"] == pytest.approx(12.5)
    # blocked steps with a chunk: i = 7, 11, 15, 23 (19 had none)
    unasked = (15.5 - 4) * 3 / (15.5 * 8)
    blocked = 4 * 3 / (15.5 * 8)
    assert got["slots_unrequested_share"] == pytest.approx(100 * unasked)
    assert got["slots_blocked_share"] == pytest.approx(100 * blocked)
    assert (
        got["slots_decoding_share"] + got["slots_filling_share"]
        + got["slots_unrequested_share"] + got["slots_blocked_share"]
    ) == pytest.approx(100.0)
    assert got["slots_by_admit_stopped_by"] == {
        "no_pages": [4.0, 4.0, 1.0, 1.0, 2.0],
        "queue_empty": [11.5, 4.0, 1.0, pytest.approx(3.5 / 11.5),
                        pytest.approx(3 - 3.5 / 11.5)],
    }
    # step + admit of every lap (``span_reduce.BOOKKEEPING``'s phases),
    # waits and the fetch left out; no lap stands out
    assert got["engine_thread_busy_share"] == pytest.approx(10.0)
    assert got["engine_thread_busy_share_long_laps"] == 0.0
    assert got["self_s_by_phase"]["areal.engine.harvest.wait"] == pytest.approx(
        19.5 * 0.85
    )
    # the slice alone: 2.0 s of decode programs in 2.4 s busy; and the
    # estimate: 15.5 chunks of 0.5 s in 19.5 s, IF busy 80% throughout
    assert got["fill_busy_share_slice"] == pytest.approx(100 * (1 - 2.0 / 2.4))
    assert got["fill_busy_share_estimate"] == pytest.approx(
        100 * (1 - 15.5 * 0.5 / (19.5 * 0.8))
    )
    assert got["tokens_emitted"] == pytest.approx(19.5 * 256)
    assert got["compiled"] == [[13, 1, 0.5]]
    assert len(got["longest_laps"]) == 10
    assert got["longest_laps"][0][2:] == ["areal.engine.harvest.wait", 0.85]
    # without a capture's numbers the device's figures are left out
    bare = step_log.engine_account(header, records, 105.5, 125.0)
    assert bare["fill_busy_share_slice"] is None
    assert bare["fill_busy_share_estimate"] is None
    # a lap whose fold blocked half a second behind the device: in the
    # share (which is "not in a named wait"), and named as a long lap's
    records[10]["self_s"] = dict(
        records[10]["self_s"], **{"areal.engine.harvest.fold": 0.5}
    )
    long_ = step_log.engine_account(header, records, 105.5, 125.0)
    assert long_["engine_thread_busy_share"] == pytest.approx(
        10.0 + 100 * 0.5 / 19.5
    )
    assert long_["engine_thread_busy_share_long_laps"] == pytest.approx(
        100 * 0.6 / 19.5
    )
    # fewer than eight laps in the stretch: nothing to say
    assert step_log.engine_account(header, records, 100.0, 107.0) is None
    assert step_log.engine_account(header, [], 100.0, 130.0) is None


def _train_records():
    """Forty batches of 0.09 s, 10 ms apart; after the twentieth the next
    begins 1.0 s later (a stall of the caller's)."""
    out, t = [], 200.0
    for i in range(40):
        out.append({
            "seq": i + 1, "batch": i + 1, "t0": t, "t1": t + 0.09,
            "self_s": {"areal.train.batch": 0.01, "areal.train.sync": 0.08},
            "compiles": 0, "compile_s": 0.0, "real_tokens": 8000,
            "padded_slots": 8192, "rows": 2, "row_len": 4096, "n_mbs": 1,
            "attn_blocks_run": 30, "attn_blocks_causal": 72, "version": i + 1,
        })
        t += 0.1 if i != 19 else 1.0
    return {"log": "train.actor", "lap": "areal.train.batch", "dropped": 0,
            "phases": [], "laps": 40}, out


def test_the_trainers_account_on_a_made_up_log():
    header, records = _train_records()
    a, b = records[0]["t0"], records[-1]["t1"]
    got = step_log.train_account(header, records, a, b)
    assert got["batches"] == 40 and got["lap_median_s"] == pytest.approx(0.1)
    # 38 gaps of 10 ms and one of 910
    assert got["between_batches_ms"] == pytest.approx((38 * 10 + 910) / 39)
    assert got["between_batches_max_ms"] == pytest.approx(910.0)
    assert got["step_stall_share"] == pytest.approx(100 * 1.0 / (b - a))
    ((seq, lasted, held_by, held_s),) = got["stalled"]
    assert (seq, held_by) == (20, "between")
    assert lasted == pytest.approx(1.0) and held_s == pytest.approx(0.91)
    assert got["versions"] == [1, 40]
    # a plain stretch: no stall, the gaps alone
    plain = step_log.train_account(header, records[:20], a, records[19]["t1"])
    assert plain["step_stall_share"] == 0.0 and plain["stalled"] == []
    assert plain["between_batches_ms"] == pytest.approx(10.0)
    # the stretch's edge cuts the stall: the part inside counts
    cut = step_log.train_account(header, records, a, records[19]["t0"] + 0.5)
    assert cut["step_stall_share"] == pytest.approx(
        100 * 0.5 / (records[19]["t0"] + 0.5 - a)
    )


def _ctx(tmp_path, with_capture=True):
    work = tmp_path / "out" / "work"
    work.mkdir(parents=True, exist_ok=True)
    if with_capture:
        d = tmp_path / "out" / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        (d / "made_up.xplane.pb").write_bytes(b"")
    return types.SimpleNamespace(
        work_dir=str(work), seconds=50.0, traffic={"trace_seconds": 3.0},
        trace={"busy_s": 2.4, "window_s": 3.0},
    )


def _made_up_run(monkeypatch, logs, capture):
    monkeypatch.setattr(step_log, "_accounts", {})
    monkeypatch.setattr(
        step_log, "records_in_process", lambda log: logs.get(log)
    )
    monkeypatch.setattr(step_log, "capture_of", lambda path: capture)
    monkeypatch.setattr(step_log, "decode_program_s", lambda path: (4, 2.0))


def test_the_six_readers_on_a_made_up_run(tmp_path, monkeypatch, capsys):
    header, records = _engine_records()
    for r in records:  # every step alike: the shares are round numbers
        r.update(slots_parked=0, slots_empty=3, decode_chunks=1)
        r["admit_stopped_by"] = "no_slot" if r["step"] % 3 == 0 else "queue_empty"
    theader, trecords = _train_records()
    trecords = trecords[:20] + [
        dict(r, t0=r["t0"] - 0.9 + 0.105, t1=r["t1"] - 0.9 + 0.105)
        for r in trecords[20:]
    ]  # (one lap of 0.205 s among 0.1 s ones: 0.205 of 4.095 s... see below)
    capture = step_log.Capture(t0=113.0, t1=116.0, offset=0.0, marks=7)
    _made_up_run(monkeypatch, {"engine": (header, records)}, capture)
    ctx = _ctx(tmp_path)
    # the stretch: 21.5 s either side of the capture, 91.5 to 137.5: every
    # one of the thirty laps lies inside, the 30 s they last are not all of it
    got = {name: _reader(name).value(ctx) for name in ENGINE_READERS}
    assert got["slots_filling_share"] == pytest.approx(12.5)
    assert got["slots_unrequested_share"] == pytest.approx(100 * 20 * 3 / 240)
    assert got["slots_blocked_share"] == pytest.approx(100 * 10 * 3 / 240)
    assert got["engine_thread_busy_share"] == pytest.approx(100 * 3.0 / 46.0)
    # one line a run, whichever reader came first, with the stretch's ends
    (line,) = [
        json.loads(ln) for ln in capsys.readouterr().out.splitlines()
        if '"step_log"' in ln
    ]
    assert line["log"] == "engine" and line["laps"] == 30
    assert line["stretch"] == [91.5, 137.5] and line["capture"] == [113.0, 116.0]
    # the device's two figures are on the line, labelled, and no metric's
    assert line["fill_busy_share_estimate"] == pytest.approx(
        100 * (1 - 30 * 0.5 / (46.0 * 0.8))
    )
    assert not os.path.exists(
        os.path.join(BENCH, "layer_metrics", "fill_busy_share.py")
    )
    # no trainer in this process: its readers have nothing to read
    for name in TRAIN_READERS:
        assert _reader(name).value(ctx) is None
    # ... and the trainer's, where there is one
    capture = step_log.Capture(t0=201.0, t1=204.0, offset=0.0, marks=3)
    _made_up_run(monkeypatch, {"train": (theader, trecords)}, capture)
    ctx = _ctx(tmp_path / "train")
    a, b = 201.0 - 21.5, 204.0 + 21.5
    assert _reader("train_between_batches_ms").value(ctx) == pytest.approx(
        (38 * 10 + 115) / 39
    )
    assert _reader("train_step_stall_share").value(ctx) == pytest.approx(
        100 * 0.205 / (b - a)
    )
    for name in ENGINE_READERS:
        assert _reader(name).value(ctx) is None


def test_readers_return_none_where_there_is_nothing_to_read(
    tmp_path, monkeypatch
):
    header, records = _engine_records()
    capture = step_log.Capture(t0=113.0, t1=116.0, offset=0.0, marks=7)
    names = list(ENGINE_READERS) + list(TRAIN_READERS)
    # without a capture (an untraced run; test_benchmark.py's made-up
    # context, which has no work directory at all)
    _made_up_run(monkeypatch, {"engine": (header, records)}, capture)
    bare = types.SimpleNamespace(trace={"busy_s": 2.0, "window_s": 4.0},
                                 window={"counters": {}})
    no_capture = _ctx(tmp_path / "a", with_capture=False)
    for name in names:
        assert _reader(name).value(bare) is None
        assert _reader(name).value(no_capture) is None
    # a capture without a mark that carries the host clock (the parent of
    # PR 51 under this PR's benchmark files), or a program without records
    _made_up_run(monkeypatch, {"engine": (header, records)}, None)
    for name in names:
        assert _reader(name).value(_ctx(tmp_path / "b")) is None
    _made_up_run(monkeypatch, {}, capture)
    for name in names:
        assert _reader(name).value(_ctx(tmp_path / "c")) is None
    from areal_tpu.observability import tracing

    monkeypatch.setattr(step_log, "_accounts", {})
    monkeypatch.undo()
    monkeypatch.delattr(tracing, "step_logs")
    assert step_log.records_in_process("engine") is None
    # fewer than eight laps in the stretch
    _made_up_run(monkeypatch, {"engine": (header, records[:5])}, capture)
    for name in ENGINE_READERS:
        assert _reader(name).value(_ctx(tmp_path / "d")) is None


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_toy_runs_capture_file_and_command_line(tmp_path, capsys):
    """A toy engine under a CPU profiler session: the capture's place on
    the host's clock from its marks, the records in process, the file the
    server would write, and ``python3 -m benchmark.lib.step_log`` on it."""
    import jax

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config
    from benchmark.lib import span_reduce

    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=8, kv_cache_len=128, chunk_size=8,
        sampling=SamplingParams(greedy=True), stop_tokens=(),
        cache_mode="paged", page_size=16, prefill_chunk_tokens=32,
    )

    def serve(tag, new=12):
        for g, plen in enumerate((20, 37)):
            prompt = [6 + (g + i) % 50 for i in range(plen)]
            for i in range(3):
                eng.submit(APIGenerateInput(
                    qid=f"{tag}{g}-{i}", prompt_ids=prompt, input_ids=prompt,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=new + 8 * i, greedy=True
                    ),
                ))
        while eng.has_work:
            eng.step()

    serve("before")
    trace_dir = tmp_path / "out" / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        serve("traced", new=70)  # (a dozen steps)
    finally:
        jax.profiler.stop_trace()
    first_traced = eng._step_seq
    serve("after")
    (tmp_path / "out" / "work").mkdir()
    ctx = types.SimpleNamespace(
        work_dir=str(tmp_path / "out" / "work"), seconds=50.0,
        traffic={"trace_seconds": 3.0}, trace={},
    )
    xplane = span_reduce.xplane_of(ctx)
    capture = step_log.capture_of(xplane)
    header, records = step_log.records_in_process("engine")
    assert header["max_batch"] == 8 and len(records) == eng._step_seq
    # the capture lies where the traced steps do, on the host's clock
    inside = [
        r for r in records
        if capture.t0 - 1e-3 <= r["t0"] and r["t1"] <= capture.t1 + 1e-3
    ]
    assert inside and capture.marks >= 2 * len(inside)
    assert all(r["step"] <= first_traced for r in inside)
    assert len(inside) >= first_traced - len(records) // 3 - 2
    # the stretch of a 50 s window holds the whole toy run
    step_log._accounts.clear()
    got = step_log.account(ctx, "engine")
    assert got["laps"] == len(records) and got["dropped"] == 0
    assert got["stretch_s"] == pytest.approx(
        capture.t1 - capture.t0 + 2 * 21.5
    )
    total = (
        got["slots_decoding_share"] + got["slots_filling_share"]
        + got["slots_unrequested_share"] + got["slots_blocked_share"]
    )
    assert total == pytest.approx(100.0)
    # (no device in a CPU capture)
    assert got["fill_busy_share_slice"] is None
    assert got["fill_busy_share_estimate"] is None
    assert got["tokens_emitted"] == eng.tokens_emitted_total
    capsys.readouterr()
    # the file, and the command line with and without the capture
    path = str(tmp_path / "steps.gen_server_0.jsonl")
    eng._phases.dump(path)
    assert step_log.main([path]) == 0
    whole = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert whole[0]["header"]["max_batch"] == 8
    printed = {k: v for line in whole[2:] for k, v in line.items()}
    assert printed["laps"] == len(records)
    assert printed["tokens_emitted"] == eng.tokens_emitted_total
    assert set(printed["slots_by_admit_stopped_by"]) == {"queue_empty"}
    assert step_log.main([path, xplane, "--seconds", "1", "--trace-seconds", "1"]) == 0
    cut = {
        k: v
        for ln in capsys.readouterr().out.splitlines()[2:]
        for k, v in json.loads(ln).items()
    }
    assert 8 <= cut["laps"] <= len(inside) + 2
