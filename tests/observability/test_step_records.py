"""A record a step (``tracing.PhaseClock``'s laps): the engine's and the
trainer's window-long account, kept always, and the marks that lay it on a
capture's clock.  CPU, toy sizes."""

import glob
import json
import os
import threading
import time

import jax
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine.train_engine import TrainEngine
from areal_tpu.interfaces.sft_interface import sft_loss_fn
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.observability import tracing
from areal_tpu.observability.table import (
    ADMIT_STOPS,
    ENGINE_STEP_RECORD,
    LAP_RECORD,
    STEP_DELTAS,
    TRAIN_BATCH_RECORD,
    TRAIN_PHASES,
    admit_stop,
)
from areal_tpu.observability.tracing import PhaseClock
from tests.engine.test_phase_counters import _engine, _req, _serve_groups
from tests.engine.test_train_engine import make_sample
from tests.observability.test_phase_spans import _lint_module


def _step_until(eng, cond, max_steps=300):
    for _ in range(max_steps):
        if cond():
            return
        eng.step()
    raise AssertionError("the engine never got there")


# -- the clock ---------------------------------------------------------------


def test_a_lap_leaves_one_record_and_what_ran_between_two_is_in_the_later():
    clock = PhaseClock(["a", "b", "c"], log="test-laps")
    assert clock.lap == "a" and clock.records() == []
    with clock.phase("a"):
        clock.note(n=1)
        with clock.phase("b"):
            time.sleep(0.002)
    with clock.phase("c"):  # outside any lap: no record of its own
        time.sleep(0.002)
    clock.note(early=True)  # (between two laps: the next one's)
    with clock.phase("a"):
        clock.note(n=2)
    first, second = clock.records()
    assert (first["seq"], first["n"], second["seq"], second["n"]) == (1, 1, 2, 2)
    assert "early" not in first and second["early"] is True
    assert set(first) - {"n"} == set(LAP_RECORD) - {"quiet_laps"}
    assert first["t0"] < first["t1"] <= second["t0"] < second["t1"]
    assert set(first["self_s"]) == {"a", "b"} and first["self_s"]["b"] >= 0.002
    assert second["self_s"]["c"] >= 0.002 and "b" not in second["self_s"]
    for name in clock.names:
        assert sum(
            r["self_s"].get(name, 0.0) for r in clock.records()
        ) == pytest.approx(clock.seconds[name], abs=1e-12)
    assert clock.header() == {
        "log": "test-laps", "lap": "a", "phases": ["a", "b", "c"],
        "laps": 2, "dropped": 0,
    }
    assert not hasattr(clock, "reset")  # (no caller since bench.py went)


def test_the_ring_drops_the_oldest_and_counts_it(monkeypatch):
    monkeypatch.setattr(tracing, "LAPS_KEPT", 4)
    clock = PhaseClock(["a"])
    for i in range(7):
        with clock.phase("a"):
            clock.note(i=i)
    kept = clock.records()
    assert [r["seq"] for r in kept] == [4, 5, 6, 7]
    assert [r["i"] for r in kept] == [3, 4, 5, 6]
    assert (clock.laps, clock.dropped) == (7, 3)
    assert clock.header()["dropped"] == 3


def test_quiet_laps_are_folded_into_one_record():
    clock = PhaseClock(["a", "b"])
    with clock.phase("a"):
        clock.note(n=0)
    for i in range(1, 6):  # five laps in which nothing moved
        with clock.phase("a"):
            clock.note(n=i)
            clock.quiet()
            with clock.phase("b"):
                pass
    held = clock.records()[-1]  # (a reader's copy stays as it was)
    with clock.phase("a"):
        clock.note(n=6)
        clock.quiet()
    with clock.phase("a"):
        clock.note(n=7)
    with clock.phase("a"):
        clock.note(n=8)
        clock.quiet()  # (the record before it is not quiet: its own)
    busy, idle, after, alone = clock.records()
    assert [r["seq"] for r in (busy, idle, after, alone)] == [1, 2, 3, 4]
    assert "quiet_laps" not in busy and "quiet_laps" not in after
    assert (idle["quiet_laps"], idle["n"]) == (6, 6)
    assert (held["quiet_laps"], held["n"]) == (5, 5)
    assert (alone["quiet_laps"], alone["n"]) == (1, 8)
    assert busy["t1"] <= idle["t0"] < idle["t1"] <= after["t0"]
    assert idle["t0"] == held["t0"] and idle["t1"] > held["t1"]
    assert (clock.laps, clock.dropped) == (4, 0)
    for name in clock.names:
        assert sum(
            r["self_s"].get(name, 0.0) for r in clock.records()
        ) == pytest.approx(clock.seconds[name], abs=1e-12)


def test_an_idle_engines_polls_are_one_record():
    """A server polls its engine without a pause: a few hundred steps a
    second with nothing to do, which would push a window's steps out of
    the ring in the half minute after it."""
    eng = _engine("paged")
    for _ in range(50):
        eng.step()
    (idle,) = eng._phases.records()
    assert (idle["quiet_laps"], idle["step"]) == (50, 50)
    assert idle["slots_empty"] == 8 and idle["admit_stopped_by"] == "queue_empty"
    _serve_groups(eng)
    for _ in range(30):
        eng.step()
    eng.pause()
    for _ in range(3):  # (the paused branch sleeps 10 ms a step)
        eng.step()
    records = eng._phases.records()
    assert records[0]["quiet_laps"] == 50 and records[-1]["quiet_laps"] == 33
    assert all("quiet_laps" not in r for r in records[1:-1])
    assert records[1]["step"] == 51 and records[-1]["step"] == eng._step_seq
    assert len(records) == eng._step_seq - 50 - 33 + 2
    assert sum(r["tokens_emitted"] for r in records) == eng.tokens_emitted_total
    for name, total in eng.phase_seconds().items():
        assert sum(
            r["self_s"].get(name, 0.0) for r in records
        ) == pytest.approx(total, abs=1e-9)


def test_step_logs_keep_the_newest_clock_of_a_name_and_outlive_its_owner():
    import gc

    old = PhaseClock(["a"], log="test-newest")
    new = PhaseClock(["a"], log="test-newest")
    assert tracing.step_logs()["test-newest"] is new
    PhaseClock(["a"])  # (a clock without a log name is nobody's to find)
    with new.phase("a"):
        pass
    del old, new
    gc.collect()
    assert [r["seq"] for r in tracing.step_logs()["test-newest"].records()] == [1]


def test_a_reader_copies_while_the_owner_appends():
    clock = PhaseClock(["a"])
    stop = threading.Event()
    seen = []

    def read():
        while not stop.is_set():
            seen.append([r["seq"] for r in clock.records()])

    t = threading.Thread(target=read)
    t.start()
    for _ in range(2000):
        with clock.phase("a"):
            pass
    stop.set()
    t.join()
    assert all(s == list(range(1, len(s) + 1)) for s in seen)
    assert clock.laps == 2000


def test_a_compile_is_counted_in_the_lap_it_fell_in():
    clock = PhaseClock(["a"])
    with clock.phase("a"):
        pass
    with clock.phase("a"):
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    with clock.phase("a"):
        pass
    quiet, compiled, after = clock.records()
    assert quiet["compiles"] == after["compiles"] == 0
    assert compiled["compiles"] >= 1 and compiled["compile_s"] > 0


# -- the engine's record -----------------------------------------------------


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_a_runs_records_sum_to_its_totals(mode):
    eng = _engine(mode)
    _serve_groups(eng)
    records = eng._phases.records()
    assert [r["step"] for r in records] == list(range(1, eng._step_seq + 1))
    assert [r["seq"] for r in records] == [r["step"] for r in records]
    for name, total in eng.phase_seconds().items():
        assert sum(
            r["self_s"].get(name, 0.0) for r in records
        ) == pytest.approx(total, abs=1e-9), name
    assert sum(r["tokens_emitted"] for r in records) == eng.tokens_emitted_total
    assert sum(r["rows_admitted"] for r in records) == 6
    assert sum(r["rows_finished"] for r in records) == 6
    assert sum(r["decode_chunks"] for r in records) == eng.chunks_total
    assert sum(r["rows_planned"] for r in records) == eng.decode_rows_planned_total > 0
    assert sum(r["fill_programs"] for r in records) == eng.prefill_calls
    assert sum(r["fill_tokens"] for r in records) == eng.prefill_tokens_total
    for r in records:
        assert (
            r["slots_decoding"] + r["slots_filling"] + r["slots_parked"]
            + r["slots_empty"]
        ) == eng.max_batch
        assert r["decode_chunks"] in (0, 1) and r["chunk_size"] == 8
        assert r["decode_rows"] <= eng.max_batch * r["decode_chunks"]
        # every row of a snapshot holds its prompt: the paged kernel's
        # decode grid visits them all, and no other slot
        assert r["rows_planned"] == r["decode_rows"]
        assert r["admit_stopped_by"] in ADMIT_STOPS
        assert set(r) <= set(LAP_RECORD) | set(ENGINE_STEP_RECORD)
        assert set(STEP_DELTAS) <= set(r)
    # six rows decode at once at most, and the pages go back
    assert max(r["slots_decoding"] for r in records) == 6
    assert records[-1]["slots_empty"] + records[-1]["slots_parked"] == 8
    assert eng.pages_live == 0
    assert eng._phases.header()["max_batch"] == 8


def test_queue_empty_no_slot_and_held_are_met():
    eng = _engine("paged", max_batch=2)
    prompt = [7 + i % 40 for i in range(20)]
    for i in range(3):  # three requests of different prompts, two slots
        eng.submit(_req(f"q{i}", prompt[i:], 6))
    eng.step()
    r = eng._phases.records()[-1]
    assert (r["admit_stopped_by"], r["pending"]) == ("no_slot", 1)
    assert r["rows_admitted"] == 2 and r["slots_empty"] == 0
    eng.hold_admissions = True
    eng.step()
    r = eng._phases.records()[-1]
    assert (r["admit_stopped_by"], r["pending"]) == ("held", 1)
    eng.hold_admissions = False
    _step_until(eng, lambda: not eng.has_work)
    last = eng._phases.records()[-1]
    assert (last["admit_stopped_by"], last["pending"]) == ("queue_empty", 0)
    assert sum(r["rows_admitted"] for r in eng._phases.records()) == 3


def test_no_pages_is_met_where_the_pool_is_what_runs_out():
    # 10 pages of 16: one prompt of 100 tokens holds 7, the next cannot fit
    eng = _engine(
        "paged", max_batch=4, kv_pool_tokens=160, prefix_cache=False
    )
    assert eng.n_blocks == 10
    for i in range(2):
        eng.submit(_req(f"q{i}", [5 + (i + j) % 50 for j in range(100)], 4))
    eng.step()
    r = eng._phases.records()[-1]
    assert (r["admit_stopped_by"], r["pending"]) == ("no_pages", 1)
    assert r["rows_admitted"] == 1 and r["slots_empty"] == 3
    _step_until(eng, lambda: not eng.has_work)
    assert len(eng.drain_results()) == 2
    assert sum(r["rows_admitted"] for r in eng._phases.records()) == 2


def test_the_paused_branch_records_too():
    eng = _engine("paged")
    prompt = [9 + i % 30 for i in range(20)]
    eng.submit(_req("a", prompt, 20))
    _step_until(eng, lambda: eng.n_decoding == 1 and len(eng._ring) > 0)
    eng.pause()
    before = eng._phases.records()[-1]
    eng.step()  # drains the ring, admits nothing
    eng.submit(_req("b", prompt[3:], 4))
    eng.step()
    drained, held = eng._phases.records()[-2:]
    assert drained["step"] == before["step"] + 1
    assert drained["ring"] == 0 and drained["tokens_emitted"] > 0
    assert drained["admit_stopped_by"] == "queue_empty"
    assert (held["admit_stopped_by"], held["pending"]) == ("held", 1)
    assert held["rows_admitted"] == held["decode_chunks"] == 0
    eng.resume()
    _step_until(eng, lambda: not eng.has_work)
    records = eng._phases.records()
    assert sum(r["tokens_emitted"] for r in records) == eng.tokens_emitted_total
    for name, total in eng.phase_seconds().items():
        assert sum(
            r["self_s"].get(name, 0.0) for r in records
        ) == pytest.approx(total, abs=1e-9)


def test_every_admit_stop_used_is_declared_and_every_declared_one_used():
    lint = _lint_module()
    sites = lint.collect_admit_stop_sites()
    assert set(sites) == set(ADMIT_STOPS)
    assert all(
        where[0].endswith("inference_server.py")
        for found in sites.values() for where in found
    )
    documented = lint.collect_documented_row("admit_stopped_by")
    assert lint.admit_stop_vocabulary_problems(sites, ADMIT_STOPS, documented) == []
    assert lint.record_field_problems() == []
    # ... and the lint fails both ways
    made_up = lint.collect_admit_stop_sites(
        sources={"mod.py": "a = admit_stop('no_luck')\nb = admit_stop(why)\n"}
    )
    problems = lint.admit_stop_vocabulary_problems(
        made_up, ADMIT_STOPS, set(ADMIT_STOPS) | {"stale"}
    )
    assert any("'no_luck'" in p and "mod.py:1" in p for p in problems)
    assert any("non-literal" in p and "mod.py:2" in p for p in problems)
    assert any("'held' is never used" in p for p in problems)
    assert any("'stale'" in p for p in problems)
    with pytest.raises(ValueError):
        admit_stop("no_luck")


# -- on a capture's clock ------------------------------------------------------


def _marks(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [
                    (e.start_ns * 1e-9, e.name, dict(e.stats))
                    for e in line.events
                    if e.name.startswith("areal.phase.")
                ]
    return path, sorted(out, key=lambda m: m[0])


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_the_marks_carry_the_host_clock_and_the_laps_seq(tmp_path):
    eng = _engine("paged")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    eng.step()  # a lap before the capture: the marks' seq does not start at 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_groups(eng)
    finally:
        jax.profiler.stop_trace()
    _, marks = _marks(str(tmp_path))
    assert marks and all(
        isinstance(m[2]["t"], float) and m[2]["seq"] >= 2 for m in marks
    )
    # any mark gives the offset between the two clocks: two agree to 1 ms
    # (they agree to a few microseconds on a quiet machine)
    offsets = [m[2]["t"] - m[0] for m in marks]
    assert max(offsets) - min(offsets) < 1e-3
    # a step's marks carry its record's seq, and lie inside its t0..t1 once
    # moved to the host's clock
    records = {r["seq"]: r for r in eng._phases.records()}
    offset = offsets[0]
    for start, name, counts in marks:
        r = records[counts["seq"]]
        if counts["of"] == "areal.engine.step" and name == "areal.phase.end":
            assert start + offset >= r["t1"] - 1e-3
        else:
            assert r["t0"] - 1e-3 <= start + offset <= r["t1"] + 1e-3
    steps = [m for m in marks if m[2]["of"] == "areal.engine.step"]
    assert sorted({m[2]["seq"] for m in steps}) == list(
        range(2, eng._step_seq + 1)
    )


# -- the trainer's record ------------------------------------------------------


def test_the_trainer_records_a_batch_and_the_time_between_two():
    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = MeshSpec(data=1, fsdp=1, model=1).make_mesh(jax.devices()[:1])
    eng = TrainEngine(
        cfg, mesh, params,
        OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0), 100,
        name="actor",
    )
    assert tracing.step_logs()["train.actor"] is eng._phases
    for seed in (0, 1, 1):  # (the third batch is laid out as the second)
        sample = make_sample(6, 64, seed=seed, min_len=4, max_len=40)
        eng.train_batch(sample, sft_loss_fn, MicroBatchSpec(n_mbs=1))
        time.sleep(0.005)  # (what a caller does between two batches)
    records = eng._phases.records()
    assert [r["batch"] for r in records] == [1, 2, 3] == [
        r["version"] for r in records
    ]
    for r in records:
        assert set(r) == (set(LAP_RECORD) | set(TRAIN_BATCH_RECORD)) - {
            "quiet_laps"
        }
        assert r["n_mbs"] * r["rows"] * r["row_len"] == r["padded_slots"]
        assert 0 < r["real_tokens"] <= r["padded_slots"]
        assert set(r["self_s"]) == set(TRAIN_PHASES)
    for before, after in zip(records, records[1:]):
        assert after["t0"] - before["t1"] >= 0.005
    assert sum(r["padded_slots"] for r in records) == eng.padded_slots_total
    for name, total in eng._phases.seconds.items():
        assert sum(r["self_s"][name] for r in records) == pytest.approx(
            total, abs=1e-9
        )
    # the first batch compiled its step program, the third found it
    assert records[0]["compiles"] >= 1 and records[2]["compiles"] == 0


# -- the server's file ----------------------------------------------------------


def test_the_exit_hook_writes_a_file_whose_lines_parse(tmp_path, monkeypatch):
    from areal_tpu.base import constants, logging_
    from areal_tpu.system.generation_server import GenerationServerWorker

    monkeypatch.setenv("AREAL_LOG_ROOT", str(tmp_path))
    constants.set_experiment_trial_names("step-records", "t0")
    worker = GenerationServerWorker.__new__(GenerationServerWorker)
    worker.worker_name = "gen_server_7"
    worker.logger = logging_.getLogger("gen_server_7")
    worker.engine = eng = _engine("paged")
    _serve_groups(eng)
    worker._exit_hook()
    path = os.path.join(constants.get_log_path(), "steps.gen_server_7.jsonl")
    with open(path) as f:
        header, *steps = [json.loads(line) for line in f]
    assert header["log"] == "engine" and header["lap"] == "areal.engine.step"
    assert header["max_batch"] == 8 and header["dropped"] == 0
    assert header["laps"] == len(steps) == eng._step_seq
    assert steps[-1]["step"] == eng._step_seq
    assert steps == json.loads(json.dumps(eng._phases.records()))
