"""Decode-loop time attribution: the engine splits step() wall time into
host-bookkeeping vs blocked-on-device vs output-fetch, per chunk — the
numbers behind the 'is the decode gap the fetch or host bookkeeping?'
question (surfaced at /metrics, and read by the benchmark as
``engine_host_share``)."""

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


def _make_engine(mode):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=256)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(),
    )
    if mode == "paged":
        kw.update(cache_mode="paged", page_size=16, prefill_chunk_tokens=16)
    return ContinuousBatchingEngine(cfg, params, **kw)


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_timing_split_accumulates_per_chunk(mode):
    eng = _make_engine(mode)
    for i in range(3):
        eng.submit(
            APIGenerateInput(
                qid=f"q{i}",
                prompt_ids=[1, 2, 3, 4],
                input_ids=[1, 2, 3, 4],
                gconfig=GenerationHyperparameters(
                    max_new_tokens=24, temperature=1.0
                ),
            )
        )
    for _ in range(200):
        if not eng.has_work:
            break
        eng.step()
    assert not eng.has_work

    split = eng.timing_split()
    assert set(split) == {"host_s", "device_s", "fetch_s", "chunks"}
    # every harvested chunk was attributed
    assert split["chunks"] == eng.chunks_total > 0
    # wall time was actually attributed somewhere, and no bucket went
    # negative (host_s is residual-clamped)
    assert split["host_s"] > 0
    assert split["device_s"] >= 0
    assert split["fetch_s"] >= 0
    assert split["device_s"] + split["fetch_s"] > 0


def test_timing_split_in_gen_server_metrics_dict():
    """The generation server's 'metrics' command reply carries the split
    (time_host_s/time_device_s/time_fetch_s/time_chunks keys)."""
    eng = _make_engine("dense")
    eng.submit(
        APIGenerateInput(
            qid="q0",
            prompt_ids=[1, 2, 3],
            input_ids=[1, 2, 3],
            gconfig=GenerationHyperparameters(
                max_new_tokens=8, temperature=1.0
            ),
        )
    )
    for _ in range(100):
        if not eng.has_work:
            break
        eng.step()
    # mirror of GenerationServerWorker.metrics() composition
    d = {f"time_{k}": v for k, v in eng.timing_split().items()}
    assert d["time_chunks"] >= 1
    assert d["time_host_s"] > 0
