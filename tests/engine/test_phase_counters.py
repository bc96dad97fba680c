"""The always-on half of the phase spans: the engine's per-phase seconds,
its token and page counters, and the trainer's cumulative padding."""

import time

import jax
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec
from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.engine.train_engine import TrainEngine
from areal_tpu.interfaces.sft_interface import sft_loss_fn
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.observability.table import ENGINE_PHASES
from tests.engine.test_train_engine import make_sample

HOST_PHASES = [
    p for p in ENGINE_PHASES
    if p not in ("areal.engine.harvest.wait", "areal.engine.harvest.fetch")
]


def _engine(mode="paged", head_dim=8, **kw):
    cfg = tiny_config(
        vocab_size=64, max_position_embeddings=512, head_dim=head_dim
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    args = dict(
        max_batch=8, kv_cache_len=128, chunk_size=8,
        sampling=SamplingParams(greedy=True), stop_tokens=(),
    )
    if mode == "paged":
        args.update(cache_mode="paged", page_size=16, prefill_chunk_tokens=32)
    args.update(kw)
    return ContinuousBatchingEngine(cfg, params, **args)


def _req(qid, prompt, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=max_new, greedy=True),
    )


def _serve_groups(eng, groups=((20, 3), (37, 3)), max_new=12):
    """Submit ``groups`` of (prompt length, samples) and step to the end;
    returns the wall seconds spent inside ``step()`` and the replies."""
    n = 0
    for g, (plen, samples) in enumerate(groups):
        prompt = [6 + (g + i) % 50 for i in range(plen)]
        for i in range(samples):
            eng.submit(_req(f"g{g}-{i}", prompt, max_new + 8 * i))
            n += 1
    wall = 0.0
    for _ in range(500):
        if not eng.has_work:
            break
        tik = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - tik
    assert not eng.has_work
    replies = eng.drain_results()
    assert len(replies) == n
    return wall, replies


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_phase_seconds_add_up_to_the_steps_wall_time(mode):
    eng = _engine(mode)
    wall, _ = _serve_groups(eng)
    sec = eng.phase_seconds()
    assert set(sec) == set(ENGINE_PHASES)
    assert all(v >= 0 for v in sec.values())
    assert sum(sec.values()) == pytest.approx(wall, rel=0.02)
    # phases that ran have time, and nothing of the paged path ran dense
    assert sec["areal.engine.step"] > 0 and sec["areal.engine.admit"] > 0
    assert sec["areal.engine.decode.dispatch"] > 0
    assert sec["areal.engine.fill.first_token_wait"] > 0
    assert (sec["areal.engine.fill.dispatch"] > 0) == (mode == "paged")
    assert sec["areal.engine.swap"] == 0


def test_timing_split_is_the_sum_of_the_phases():
    eng = _engine()
    _serve_groups(eng)
    split, sec = eng.timing_split(), eng.phase_seconds()
    assert set(split) == {"host_s", "device_s", "fetch_s", "chunks"}
    assert split["host_s"] == pytest.approx(sum(sec[p] for p in HOST_PHASES))
    assert split["device_s"] == sec["areal.engine.harvest.wait"]
    assert split["fetch_s"] == sec["areal.engine.harvest.fetch"]
    assert split["chunks"] == eng.chunks_total > 0


def test_a_paused_step_counts_its_drain_and_not_its_sleep():
    eng = _engine()
    eng.pause()
    tik = time.perf_counter()
    for _ in range(3):
        assert eng.step() == 0
    slept = time.perf_counter() - tik
    assert slept >= 0.03
    assert sum(eng.phase_seconds().values()) < 0.5 * slept


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_tokens_emitted_total_equals_the_tokens_in_the_replies(mode):
    eng = _engine(mode)
    returned = []
    step = eng.step
    eng.step = lambda: returned.append(step()) or returned[-1]
    _, replies = _serve_groups(eng)
    in_replies = sum(len(r.output_ids) for r in replies.values())
    assert eng.tokens_emitted_total == in_replies == eng.gen_tokens_total
    # step()'s return value keeps its meaning: every token but the first
    # of each sequence, which the prefill hands over
    assert sum(returned) == in_replies - len(replies)


def test_tokens_emitted_total_moves_before_a_row_finishes():
    eng = _engine()
    eng.submit(_req("q", [7, 8, 9, 10], 64))
    seen = []
    while eng.has_work:
        eng.step()
        seen.append((eng.tokens_emitted_total, eng.gen_tokens_total))
    assert seen[-1] == (64, 64)
    # mid-flight the emitted count has moved and the finished count has not
    assert any(0 < emitted < 64 and done == 0 for emitted, done in seen)


def test_pages_live_counts_a_shared_prompt_once_and_returns_to_zero():
    eng = _engine(max_batch=4)
    assert (eng.pages_live, eng.pages_total) == (0, eng.n_blocks)
    prompt = [6 + i % 50 for i in range(33)]  # 2 full pages + 1 token
    for i in range(4):
        eng.submit(_req(f"g-{i}", prompt, 6))
    eng._admit_paged()
    # four rows filling ONE prompt: its three pages, once
    assert eng.pages_live == 3
    peak = 0
    while eng.has_work:
        eng.step()
        peak = max(peak, eng.pages_live)
    # decoding: 2 shared full pages + a private tail each, grown by at
    # most one page a row; never the 4 x 3 an unshared count would give
    assert 6 <= peak <= 10
    # every row is finished and parked: nothing is live, though the pool
    # still holds their pages
    assert eng.n_parked == 4
    assert eng.pages_live == 0
    assert eng.free_pool_blocks < eng.n_blocks


def test_pages_are_zero_on_the_dense_cache():
    eng = _engine("dense")
    _serve_groups(eng)
    assert (eng.pages_live, eng.pages_total) == (0, 0)


class _RecordingSpan:
    """Stands in for ``tracing.phase``: keeps what each span was given."""

    seen = []

    def __init__(self, name, **counts):
        self.name, self.counts = name, dict(counts)

    def __enter__(self):
        _RecordingSpan.seen.append(self)
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        self.counts.update(counts)


def test_train_engine_cumulative_slots_are_the_sum_of_each_calls(monkeypatch):
    from areal_tpu.observability import tracing

    monkeypatch.setattr(_RecordingSpan, "seen", [])
    # (the trainer's spans are its PhaseClock's, which makes them here)
    monkeypatch.setattr(
        tracing, "_annotation", lambda name, c: _RecordingSpan(name, **c)
    )
    cfg = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = MeshSpec(data=1, fsdp=1, model=1).make_mesh(jax.devices()[:1])
    eng = TrainEngine(
        cfg, mesh, params,
        OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0), 100,
    )
    assert (eng.padded_slots_total, eng.real_tokens_total) == (0, 0)
    slots, tokens, fracs = [], [], []
    for seed, (bs, n_mbs) in enumerate([(8, 1), (5, 2), (12, 3)]):
        sample = make_sample(bs, 64, seed=seed, min_len=4, max_len=40)
        eng.train_batch(sample, sft_loss_fn, MicroBatchSpec(n_mbs=n_mbs))
        slots.append(eng.last_padded_slots)
        tokens.append(
            sum(l for ls in sample.seqlens["packed_input_ids"] for l in ls)
        )
        fracs.append(eng.last_padding_frac)
    assert eng.padded_slots_total == sum(slots) > 0
    assert eng.real_tokens_total == sum(tokens)
    # the last call's fraction stays the last call's
    assert eng.last_padding_frac == pytest.approx(1 - tokens[-1] / slots[-1])
    overall = 1 - eng.real_tokens_total / eng.padded_slots_total
    assert min(fracs) <= overall <= max(fracs)
    assert np.isfinite(overall)
    # the span of each call says what was laid out: the counts the
    # benchmark's train_pad_share_all reads, and the [rows, row_len] chosen
    spans = [s for s in _RecordingSpan.seen if s.name == "areal.train.batch"]
    assert [s.counts["padded_slots"] for s in spans] == slots
    assert [s.counts["real_tokens"] for s in spans] == tokens
    # sequences of 4-39 tokens: rows of 32 or 64, the count bucketed (3 -> 4)
    assert [s.counts["n_mbs"] for s in spans] == [1, 2, 4]
    for s in spans:
        c = s.counts
        assert c["row_len"] in (32, 64)
        assert c["n_mbs"] * c["rows"] * c["row_len"] == c["padded_slots"]
        # the SFT loss is a sum over tokens: its step programs take each
        # chunk's gradient in place, three head products a token
        assert c["loss_head_products"] == 3
    # ... and the clock's record of each call holds the same counts,
    # traced or not
    records = eng._phases.records()
    assert [r["batch"] for r in records] == [1, 2, 3]
    for r, s in zip(records, spans):
        assert {k: r[k] for k in r if k in s.counts} == {
            k: v for k, v in s.counts.items() if k != "loss_head_products"
        }
        assert r["version"] == r["batch"]


@pytest.mark.parametrize("pack", [True, False])
def test_train_batch_counts_the_attention_block_pairs_its_layout_runs(
    monkeypatch, pack
):
    """``areal.train.batch`` carries ``attn_blocks_run`` and
    ``attn_blocks_causal`` of the layout just built (the flash kernels'
    own rule, ``ops/flash_attention.block_ranges``, on the host's ids):
    the pairs under the diagonal in which a q and a kv slot share an id, by
    brute force, of ``n (n + 1) / 2`` a row; the engine keeps their sums."""
    from areal_tpu.observability import tracing
    from tests.ops.test_flash_attention import pairs_that_meet

    monkeypatch.setattr(_RecordingSpan, "seen", [])
    monkeypatch.setattr(
        tracing, "_annotation", lambda name, c: _RecordingSpan(name, **c)
    )
    cfg = tiny_config(vocab_size=64, max_position_embeddings=2048)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = MeshSpec(data=1, fsdp=1, model=1).make_mesh(jax.devices()[:1])
    eng = TrainEngine(
        cfg, mesh, params,
        OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0), 100,
        pack_sequences=pack,
    )
    laid_out = []
    stack = eng._stack_batches

    def recording_stack(*args):
        stacked, pbs = stack(*args)
        laid_out.append(stacked["seg_ids"])
        return stacked, pbs

    monkeypatch.setattr(eng, "_stack_batches", recording_stack)
    assert (eng.attn_blocks_run_total, eng.attn_blocks_causal_total) == (0, 0)
    # sequences of 300-1,100 tokens: rows of 2,048 slots, four blocks each
    for seed, n_mbs in enumerate([1, 2]):
        sample = make_sample(5, 64, seed=seed, min_len=300, max_len=1100)
        eng.train_batch(sample, sft_loss_fn, MicroBatchSpec(n_mbs=n_mbs))
    spans = [s for s in _RecordingSpan.seen if s.name == "areal.train.batch"]
    assert len(spans) == len(laid_out) == 2
    run_total = causal_total = 0
    for span, seg in zip(spans, laid_out):
        rows = seg.reshape(-1, seg.shape[-1])
        n = rows.shape[1] // 512
        assert n == span.counts["row_len"] // 512 >= 2
        run = sum(len(pairs_that_meet(row, 512)) for row in rows)
        assert span.counts["attn_blocks_run"] == run
        assert span.counts["attn_blocks_causal"] == len(rows) * n * (n + 1) // 2
        assert 0 < run < span.counts["attn_blocks_causal"]
        run_total += run
        causal_total += span.counts["attn_blocks_causal"]
    assert eng.attn_blocks_run_total == run_total
    assert eng.attn_blocks_causal_total == causal_total


class _Span:
    """A span that records: what ``_count_dispatch`` meets while a
    profiler session is on."""

    def __init__(self):
        self.counts = {}

    def is_enabled(self):
        return True

    def set_metadata(self, **kw):
        self.counts.update(kw)


@pytest.mark.parametrize(
    "mode,kw,page,tile",
    [
        ("paged", {}, 16, 16),  # a page of 16 tokens is its own tile
        # heads of 8: 64 B a token, too little to cut a page of 1,024
        ("paged", dict(page_size=1024, kv_cache_len=2048,
                       kv_pool_tokens=8192), 1024, 1024),
        # 2 kv heads x 128 x 4 B: a tile of 256 tokens is 256 KiB
        ("paged", dict(page_size=1024, kv_cache_len=2048, head_dim=128,
                       kv_pool_tokens=8192), 1024, 256),
        ("dense", {}, 128, 128),  # one row, one "page", nothing to cut
    ],
)
def test_dispatch_span_counts_the_tiles_the_kernel_reads(mode, kw, page, tile):
    """``tiles_attended`` / ``tile_tokens`` on ``areal.engine.decode.dispatch``
    against a hand count: the tile is the kernel module's (from the
    pool's shape), the tiles a row costs ``ceil(context / tile)``."""
    from areal_tpu.ops.paged_attention import page_tile

    eng = _engine(mode, **kw)
    for i, plen in enumerate((20, 37, 5)):
        eng.submit(_req(f"r{i}", [6 + (i + k) % 50 for k in range(plen)], 40))
    while eng.n_decoding < 3:
        eng.step()
    snapshot = [(i, r.epoch) for i, r in enumerate(eng.rows) if r is not None]
    # (a first token on its way to the host is among a row's tokens)
    ctx = [eng.rows[i].n_tokens for i, _ in snapshot]
    assert len(ctx) == 3 and min(ctx) > 5
    span = _Span()
    eng._count_dispatch(span, snapshot, eng.chunk_size)
    c = span.counts
    assert c["tile_tokens"] == tile and c["ctx_tokens_sum"] == sum(ctx)
    if mode == "paged":
        assert tile == page_tile(eng.k_pool.shape, eng.k_pool.dtype)
    assert c["tiles_attended"] == sum(-(-n // tile) for n in ctx)
    assert c["pages_attended"] == sum(-(-n // page) for n in ctx)
    # what the kernel reads holds what is attended, and no whole page more
    assert c["ctx_tokens_sum"] <= c["tiles_attended"] * tile
    assert c["tiles_attended"] * tile <= c["pages_attended"] * page
