"""The engine's page bookkeeping under a LOOPED stack: a page id names its
slice of every cache layer (``n_layers x loop_steps``), so sharing a
prompt's pages among its samples, copying a tail page and parking move a
looped model's pages as they move any other's; what changes is the bytes a
page, and that admission waits for PAGES with slots free."""

import dataclasses

import jax
import numpy as np

import areal_tpu.models.hf  # noqa: F401 - registers the families
from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.hf.registry import get_hf_family
from benchmark.lib import reference_ouro as ref
from tests.model.test_loop import HF


def make_engine(**kw):
    cfg = dataclasses.replace(
        get_hf_family("ouro").config_from_hf(HF), dtype="float32"
    )
    params = transformer.init_params_in_dtype(cfg, jax.random.PRNGKey(3))
    defaults = dict(
        max_batch=8, kv_cache_len=128, chunk_size=4,
        sampling=SamplingParams(greedy=True), cache_mode="paged",
        page_size=16, prefill_chunk_tokens=32,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults), cfg, params


def _req(qid, prompt, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=max_new, greedy=True),
    )


def run_until_done(eng, max_steps=500):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def test_eight_siblings_hold_their_prompts_pages_once_across_all_cache_layers():
    eng, cfg, params = make_engine()
    assert eng.k_pool.shape[0] == 9 == cfg.n_attn_layers  # 3 layers x 3 passes
    assert eng.loop_counts == dict(
        loop_steps=3, cache_layers=9, kv_bytes_per_token=9 * 2 * 4 * 16 * 4
    )
    prompt = [int(t) for t in np.arange(37) % 200 + 6]  # 2 full pages + 5
    for i in range(8):
        eng.submit(_req(f"g-{i}", prompt, 6))
    eng._admit_paged()
    assert len(eng._filling) == 1 and len(eng._filling[0].targets) == 8
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 8
    # the prompt was prefilled ONCE, over every cache layer
    assert eng.prefill_tokens_total == len(prompt)
    # 2 shared full pages + 8 tails of their own (each may have grown a page)
    used = eng.n_blocks - eng.free_pool_blocks
    assert 2 + 8 <= used <= 2 + 2 * 8
    shared = np.asarray(eng._pages._ref)
    assert (shared >= 8).sum() == 2  # the two full pages, held by all eight
    # every sibling's greedy tokens are the plain reference's (its logits
    # over the sequence they made), so every pass read ITS cache layer of
    # the shared pages and of the copied tail
    (new,) = {tuple(r.output_ids) for r in out.values()}
    assert len(new) == 6
    logits, _ = ref.forward_logits(HF, params, prompt + list(new[:-1]))
    assert tuple(np.argmax(np.asarray(logits[len(prompt) - 1 :]), -1)) == new


def test_admission_waits_for_pages_with_slots_free_and_counts_it():
    # 6 pages of 16 in all: a prompt of 40 and its 8 new tokens take 3, so
    # two rows fill the pool while six of the eight slots stand empty
    eng, *_ = make_engine(kv_pool_tokens=96, prefix_cache=False)
    eng.park_ttl_steps = 0
    assert eng.n_blocks == 8  # (one full-length row always fits: 128 / 16)
    rng = np.random.RandomState(0)
    for i in range(5):
        eng.submit(_req(f"q{i}", [int(t) for t in rng.randint(6, 200, 100)], 8))
    run_until_done(eng)
    assert len(eng.drain_results()) == 5
    assert eng.preempted_total == 0
    assert eng.admission_page_waits_total > 0
    records = [r for r in eng._phases.records() if "admit_stopped_by" in r]
    waited = [r for r in records if r.get("admission_page_waits")]
    assert sum(r["admission_page_waits"] for r in waited) == (
        eng.admission_page_waits_total
    )
    assert all(
        r["admit_stopped_by"] == "no_pages" and r["slots_empty"] > 0
        and r["pending"] > 0
        for r in waited
    )
