"""Tensor-parallel generation engine: a 2-way model-axis mesh must produce
the same greedy outputs as the single-device engine (the reference's TP
SGLang server role, realhf/impl/model/backend/sglang.py decoupled mode).

Beyond the original dense arm, the mesh-complete matrix: the PAGED pool
(block tables + chunked prefill) and the radix prefix cache (COW tail
via ``paged.copy_blocks``) run under ``mesh != None`` with token parity
against the single-device engine (ISSUE 7: this matrix had never been exercised
under a mesh — the keyed-sampler shard_map fence in engine/sampling.py
exists because this file's paged arm caught jax 0.4's legacy threefry
drawing different bits under a partitioned mesh)."""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine import inference_server
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(
        n_layers=2,
        hidden_dim=64,
        n_q_heads=4,
        n_kv_heads=2,
        head_dim=32,
        intermediate_dim=128,
        vocab_size=128,
        max_position_embeddings=256,
        dtype="float32",
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _generate(engine, n_reqs=3, max_new=8):
    rng = np.random.default_rng(0)
    gcfg = GenerationHyperparameters(max_new_tokens=max_new, greedy=True)
    for i in range(n_reqs):
        ids = rng.integers(0, 128, (5 + i,)).tolist()
        engine.submit(
            APIGenerateInput(
                qid=str(i), prompt_ids=ids, input_ids=ids, gconfig=gcfg
            )
        )
    outs = {}
    for _ in range(200):
        engine.step()
        for i in range(n_reqs):
            if str(i) not in outs:
                r = engine.try_get_result(str(i))
                if r is not None:
                    outs[str(i)] = r
        if len(outs) == n_reqs:
            break
    assert len(outs) == n_reqs, "generation did not finish"
    return outs


def test_tp2_engine_matches_single_device(model):
    cfg, params = model
    kwargs = dict(
        max_batch=4,
        kv_cache_len=256,
        chunk_size=4,
        sampling=SamplingParams(temperature=1.0),
    )
    single = ContinuousBatchingEngine(cfg, params, **kwargs)
    ref = _generate(single)

    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    tp = ContinuousBatchingEngine(cfg, params, mesh=mesh, **kwargs)
    # params actually sharded over the model axis (not silently replicated)
    q_w = tp.params["layers"]["attn"]["q"]["w"]
    assert "model" in jax.tree.leaves(q_w.sharding.spec, is_leaf=lambda x: True) or (
        q_w.sharding.shard_shape(q_w.shape) != q_w.shape
    ), q_w.sharding
    # the KV cache is sharded too (allocated directly on the mesh)
    assert tp.cache.k.sharding.shard_shape(tp.cache.k.shape) != tp.cache.k.shape
    got = _generate(tp)

    for qid in ref:
        assert ref[qid].output_ids == got[qid].output_ids, qid
        np.testing.assert_allclose(
            ref[qid].output_logprobs, got[qid].output_logprobs,
            rtol=1e-4, atol=1e-4,
        )


_PAGED = dict(cache_mode="paged", page_size=32, prefill_chunk_tokens=32)


def _assert_output_parity(ref, got):
    for qid in ref:
        assert ref[qid].output_ids == got[qid].output_ids, qid
        np.testing.assert_allclose(
            ref[qid].output_logprobs, got[qid].output_logprobs,
            rtol=1e-4, atol=1e-4,
        )


def test_tp2_paged_engine_matches_single_device(model):
    """Paged pool + block tables + chunked prefill under a TP mesh: token
    parity with the single-device paged engine, pool actually sharded."""
    cfg, params = model
    kwargs = dict(
        max_batch=4, kv_cache_len=256, chunk_size=4,
        sampling=SamplingParams(temperature=1.0), **_PAGED,
    )
    single = ContinuousBatchingEngine(cfg, params, **kwargs)
    assert single.paged
    ref = _generate(single)

    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    tp = ContinuousBatchingEngine(cfg, params, mesh=mesh, **kwargs)
    assert tp.paged
    # the KV pool's head axis is genuinely sharded over the model axis
    assert tp.k_pool.sharding.shard_shape(tp.k_pool.shape) != tp.k_pool.shape
    built = inference_server._activate_rows._cache_size()
    got = _generate(tp)
    # the rows' arrays and the sampled tokens live whole on every device
    # of the mesh, where the engine's start placed the warm-up's: serving
    # built no activation program (one met under load compiles there)
    assert inference_server._activate_rows._cache_size() == built
    assert tp.first_tokens_deferred_total > 0
    _assert_output_parity(ref, got)


@pytest.mark.slow
def test_tp2_prefix_cache_replay_matches_single_device(model):
    """Radix prefix cache under a TP mesh: the replayed prompts hit the
    cache (pinned blocks + COW tail through ``paged.copy_blocks`` on the
    sharded pool) and still produce single-device-identical tokens."""
    cfg, params = model
    kwargs = dict(
        max_batch=4, kv_cache_len=256, chunk_size=4,
        sampling=SamplingParams(temperature=1.0),
        prefix_cache=True, **_PAGED,
    )
    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    single = ContinuousBatchingEngine(cfg, params, **kwargs)
    tp = ContinuousBatchingEngine(cfg, params, mesh=mesh, **kwargs)
    for round_ in range(2):
        gcfg = GenerationHyperparameters(max_new_tokens=8, greedy=True)
        outs = {}
        for eng in (single, tp):
            rng = np.random.default_rng(0)
            for i in range(3):
                ids = rng.integers(0, 128, (5 + i,)).tolist()
                eng.submit(
                    APIGenerateInput(
                        qid=f"r{round_}-{i}", prompt_ids=ids,
                        input_ids=ids, gconfig=gcfg,
                    )
                )
            got = {}
            for _ in range(300):
                eng.step()
                for i in range(3):
                    q = f"r{round_}-{i}"
                    if q not in got:
                        r = eng.try_get_result(q)
                        if r is not None:
                            got[q] = r
                if len(got) == 3:
                    break
            outs[eng] = got
        for q in outs[single]:
            assert outs[single][q].output_ids == outs[tp][q].output_ids, q
    # round 2 re-sent round 1's prompts under fresh qids: both caches hit
    for eng in (single, tp):
        stats = eng.prefix_cache_stats()
        assert stats["hits_total"] > 0, stats
        assert stats["cached_tokens_total"] > 0, stats


def test_tp_weight_update_keeps_sharding(model):
    cfg, params = model
    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    eng = ContinuousBatchingEngine(
        cfg, params, mesh=mesh, max_batch=2, kv_cache_len=256, chunk_size=4
    )
    new_params = jax.tree.map(lambda x: x * 1.01, params)
    eng.update_weights(new_params, version=7)
    eng._apply_pending_weights()
    assert eng.version == 7
    lead = jax.tree.leaves(eng.params)[0]
    assert lead.sharding.mesh.shape.get("model") == 2
