"""The serving engine's sampled streams: every draw is keyed on (request
seed, absolute position) from a fixed base key
(``sampling.sample_logits_keyed``), so the same seed under different
chunking or pipelining gives identical sampled tokens (the split-sequence
hazard), different seeds and group rows draw independently, and a slot's
reuse does not repeat a stream.
"""

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

EOS = 5
VOCAB = 64

_cfg = tiny_config(vocab_size=VOCAB, max_position_embeddings=256)
_params = transformer.init_params(_cfg, jax.random.PRNGKey(0))


def make_engine(mode="paged", **kw):
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
    )
    if mode == "paged":
        defaults.update(
            cache_mode="paged", page_size=16, prefill_chunk_tokens=32
        )
    else:
        defaults.update(cache_mode="dense")
    defaults.update(kw)
    return ContinuousBatchingEngine(_cfg, _params, **defaults)


def run_wave(eng, prompts, budgets, tag="q", max_steps=600):
    qids = []
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        qids.append(
            eng.submit(
                APIGenerateInput(
                    qid=f"{tag}{i}", prompt_ids=p, input_ids=p,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=b, greedy=True
                    ),
                )
            )
        )
    for _ in range(max_steps):
        if not eng.has_work:
            break
        eng.step()
    assert not eng.has_work, "engine did not drain"
    return [eng.wait_result(q, timeout=5) for q in qids]


# repetitive motifs + irregular prompts
MOTIF = [7, 8, 9, 10]
PROMPTS = [
    MOTIF * 5,
    [10, 11, 12, 13, 14],
    [3, 2] * 6,
    [21, 22, 23, 24],
]


# temperature-only: top-p/top-k cutoffs sit on sorted-prob cliffs where
# the ~1e-7 reduction-order noise between chunk layouts can flip the
# FILTERED SET at a near-tie; the position-keyed draws themselves are
# chunking-invariant, and without cliffs so is the sampled stream
TEMP_SAMPLING = SamplingParams(temperature=0.8)


def _temp_wave(mode, chunk_size, pipeline_depth, seed=3):
    eng = make_engine(
        mode=mode, chunk_size=chunk_size,
        pipeline_depth=pipeline_depth, sampling=TEMP_SAMPLING, seed=seed,
    )
    outs = run_wave(eng, PROMPTS, [12, 9, 11, 10], tag=f"t{mode}_")
    return [o.output_ids for o in outs]


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_rng_stream_invariant_to_chunk_size(mode):
    """Same seed, different chunking => identical sampled tokens: the
    draw for (row, position) is keyed on exactly that, never on how many
    chunk dispatches produced the position."""
    assert _temp_wave(mode, 4, 2) == _temp_wave(mode, 8, 2)


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_rng_stream_invariant_to_pipeline_depth(mode):
    assert _temp_wave(mode, 4, 1) == _temp_wave(mode, 4, 3)


def test_rng_streams_differ_across_seeds_and_rows():
    """Sanity: position-keying must not collapse randomness — different
    seeds give different streams, and group rows at identical positions
    draw independently."""
    a = _temp_wave("paged", 4, 2, seed=3)
    b = _temp_wave("paged", 4, 2, seed=4)
    assert a != b
    eng = make_engine(sampling=TEMP_SAMPLING)
    outs = run_wave(
        eng, [PROMPTS[0], PROMPTS[0]], [12, 12], tag="grp"
    )
    assert outs[0].output_ids != outs[1].output_ids


def test_rng_slot_reuse_does_not_duplicate_same_prompt_streams():
    """Draws are keyed per REQUEST, not per cache-row slot: a 1-row
    engine serving the same prompt twice (the second request lands in
    the slot the first just freed — a GRPO sibling's shape) must draw an
    independent stream, while re-running the SAME request id reproduces
    its stream exactly."""
    p = PROMPTS[0]
    eng = make_engine(
        mode="dense", max_batch=1, sampling=TEMP_SAMPLING
    )
    (a,) = run_wave(eng, [p], [12], tag="reqA_")
    (b,) = run_wave(eng, [p], [12], tag="reqB_")
    assert a.output_ids != b.output_ids  # slot reuse, fresh randomness
    fresh = make_engine(
        mode="dense", max_batch=1, sampling=TEMP_SAMPLING
    )
    (a2,) = run_wave(fresh, [p], [12], tag="reqA_")
    assert a2.output_ids == a.output_ids  # same request id, same stream
