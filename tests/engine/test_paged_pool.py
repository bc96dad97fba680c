"""Paged-pool-specific engine behavior: block accounting, group sharing,
pool-pressure preemption, and chunked-prefill interleaving — the
capacity/latency properties the dense cache cannot express (reference
counterpart: SGLang's paged/radix cache behind
realhf/impl/model/backend/sglang.py:369)."""

import jax
import numpy as np
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


def make_engine(**kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
        cache_mode="paged",
        page_size=16,
        prefill_chunk_tokens=16,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults), cfg, params


def run_until_done(eng, max_steps=500):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _req(qid, prompt, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=max_new, greedy=True),
    )


def test_all_blocks_freed_after_drain():
    eng, *_ = make_engine()
    eng.park_ttl_steps = 0  # drop parked rows immediately
    for i in range(6):
        eng.submit(_req(f"q{i}", [i + 7, i + 8, i + 9], 6))
    run_until_done(eng)
    eng.drain_results()
    # one extra step so TTL eviction of parked rows runs
    eng.step()
    eng.step()
    assert eng.n_parked == 0
    # every non-free block is accounted for by the radix prefix cache
    # (finished sequences stay indexed for cross-request reuse) ...
    held = eng._prefix_cache.blocks_held
    assert eng.free_pool_blocks == eng.n_blocks - held
    # ... and flushing the cache returns the pool to pristine: no block
    # leaks across a full admit/park/evict cycle
    eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def test_all_blocks_freed_after_drain_cache_off():
    """With the prefix cache disabled the old invariant holds verbatim."""
    eng, *_ = make_engine(prefix_cache=False)
    eng.park_ttl_steps = 0
    for i in range(6):
        eng.submit(_req(f"q{i}", [i + 7, i + 8, i + 9], 6))
    run_until_done(eng)
    eng.drain_results()
    eng.step()
    eng.step()
    assert eng.n_parked == 0
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def test_group_sharing_uses_fewer_blocks():
    """4 samples over one 33-token prompt: full blocks are SHARED (ref 4),
    only the partial tail block is copied per member."""
    eng, *_ = make_engine(page_size=16, max_batch=4)
    prompt = list(np.arange(33) % 50 + 6)  # 2 full blocks + 1 tail token
    for i in range(4):
        eng.submit(_req(f"g-{i}", prompt, 4))
    eng._admit_paged()  # all four join ONE fill (inspect before the
    # fill advances: with nothing decoding, step() now rips through the
    # whole wave's chunks back-to-back inside one call)
    assert len(eng._filling) == 1 and len(eng._filling[0].targets) == 4
    run_until_done(eng)
    eng.drain_results()
    # prefill work: the unique prompt once (chunked), never per member
    assert eng.prefill_tokens_total == len(prompt)
    # block economy while parked: 2 shared full + 4 private tails = 6
    # blocks, vs 4 * 3 = 12 unshared
    used = eng.n_blocks - eng.free_pool_blocks
    assert eng.n_parked == 4
    assert used <= 4 * 2 + 2  # tails may have grown one block while decoding


def test_pool_pressure_preempts_and_completes():
    """A pool far smaller than max_batch * kv_cache_len: rows preempt under
    pressure, re-prefill later, and EVERY request still completes with the
    exact greedy output."""
    from areal_tpu.engine.generation import generate_tokens

    eng, cfg, params = make_engine(
        max_batch=4,
        kv_cache_len=128,
        kv_pool_tokens=160,  # 10 blocks of 16 — cannot hold 4 long rows
        page_size=16,
    )
    eng.park_ttl_steps = 0
    prompts = [list(np.arange(20) % 40 + 6 + i) for i in range(4)]
    gconfig = GenerationHyperparameters(max_new_tokens=24, greedy=True)
    ref = generate_tokens(
        params, cfg, prompts, gconfig, EOS, jax.random.PRNGKey(1)
    )
    for i, p in enumerate(prompts):
        eng.submit(_req(f"p{i}", p, 24))
    run_until_done(eng, max_steps=2000)
    for i in range(4):
        out = eng.wait_result(f"p{i}", timeout=5)
        assert out.output_ids == ref[i]["output_ids"], (
            i, eng.preempted_total
        )
    assert eng.preempted_total >= 1  # pressure actually bit


def test_chunked_prefill_interleaves_with_decode():
    """While a LONG prompt fills chunk-by-chunk, short rows keep decoding:
    the long admission never stalls decode for the whole wave."""
    eng, *_ = make_engine(
        max_batch=4, kv_cache_len=256, page_size=16,
        prefill_chunk_tokens=16, chunk_size=4,
    )
    short = [7, 8, 9]
    eng.submit(_req("s0", short, 40))
    eng.step()  # s0 admitted and decoding
    long_prompt = list(np.arange(100) % 40 + 6)
    eng.submit(_req("L", long_prompt, 4))
    fill_steps = 0
    decoded_during_fill = 0
    for _ in range(50):
        eng.step()
        if eng._filling:
            fill_steps += 1
            row = next(
                r for r in eng.rows if r is not None and r.req.qid == "s0"
            )
            decoded_during_fill = max(
                decoded_during_fill, len(row.generated)
            )
        if eng.try_get_result("L"):
            break
    # the 100-token prompt needed ceil(100/16) = 7 chunks...
    assert fill_steps >= 3
    # ...and the short row made decode progress while the fill was live
    assert decoded_during_fill > 4
    run_until_done(eng)
    eng.drain_results()


def test_kernel_path_on_tp_mesh_interpret():
    """The exact TPU code path — Pallas kernel shard_mapped over a TP-2
    mesh (kv-head axis sharded) — forced in interpret mode on CPU: greedy
    outputs must match the single-device reference path (code-review r5:
    this configuration was never exercised off-chip)."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.generation import generate_tokens

    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[7, 8, 9, 10, 11], [12, 13, 14]]
    gconfig = GenerationHyperparameters(max_new_tokens=6, greedy=True)
    ref = generate_tokens(
        params, cfg, prompts, gconfig, EOS, jax.random.PRNGKey(1)
    )

    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    eng = ContinuousBatchingEngine(
        cfg, params, mesh=mesh, max_batch=2, kv_cache_len=128,
        chunk_size=4, sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,), cache_mode="paged", page_size=16,
        prefill_chunk_tokens=16,
    )
    assert eng.paged and eng._kv_axis == "model"  # Hkv=2 divides tp=2
    eng._use_paged_kernel = True  # force the TPU path (interpret on CPU)
    for i, p in enumerate(prompts):
        eng.submit(_req(f"k{i}", p, 6))
    run_until_done(eng, max_steps=100)
    for i in range(2):
        out = eng.wait_result(f"k{i}", timeout=10)
        assert out.output_ids == ref[i]["output_ids"], (
            i, out.output_ids, ref[i]["output_ids"]
        )


def test_auto_mode_picks_paged_at_long_cache():
    cfg = tiny_config(vocab_size=64, max_position_embeddings=8192)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(
        cfg, params, max_batch=2, kv_cache_len=4096, cache_mode="auto"
    )
    assert eng.paged
    eng2 = ContinuousBatchingEngine(
        cfg, params, max_batch=2, kv_cache_len=256, cache_mode="auto"
    )
    assert not eng2.paged
    # sliding-window models stay dense even at long cache
    cfg_sw = tiny_config(
        vocab_size=64, max_position_embeddings=8192, sliding_window=128
    )
    params_sw = transformer.init_params(cfg_sw, jax.random.PRNGKey(0))
    eng3 = ContinuousBatchingEngine(
        cfg_sw, params_sw, max_batch=2, kv_cache_len=4096, cache_mode="auto"
    )
    assert not eng3.paged


# -- shared host gather/restore helpers (hier-cache spill + P/D handoff) ------


def _round_trip_pools(kv_cache_dtype):
    """gather_blocks_host -> restore_blocks_from_host round trip must be
    BIT-identical — the one property both consumers (prefix-cache host
    spill tier and the disaggregation handoff unit) stand on.  int8
    pools must carry their scale slices unrequantized."""
    from areal_tpu.models import paged

    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    rng = np.random.default_rng(7)
    pools = paged.alloc_kv_pool(cfg, 8, 4, kv_cache_dtype=kv_cache_dtype)
    k_pool, v_pool, k_scale, v_scale = pools
    # fill with non-trivial content (int8: random bytes + random scales)
    if kv_cache_dtype == "int8":
        k_pool = jax.numpy.asarray(
            rng.integers(-127, 128, k_pool.shape).astype(np.int8)
        )
        v_pool = jax.numpy.asarray(
            rng.integers(-127, 128, v_pool.shape).astype(np.int8)
        )
        k_scale = jax.numpy.asarray(
            rng.random(k_scale.shape).astype(np.float32)
        )
        v_scale = jax.numpy.asarray(
            rng.random(v_scale.shape).astype(np.float32)
        )
    else:
        k_pool = jax.numpy.asarray(
            rng.standard_normal(k_pool.shape).astype(np.float32)
        ).astype(k_pool.dtype)
        v_pool = jax.numpy.asarray(
            rng.standard_normal(v_pool.shape).astype(np.float32)
        ).astype(v_pool.dtype)
    src = [5, 1, 3]  # deliberately non-contiguous, non-pow2 count
    payload = paged.gather_blocks_host(
        k_pool, v_pool, src, k_scale=k_scale, v_scale=v_scale
    )
    want_components = 4 if kv_cache_dtype == "int8" else 2
    assert len(payload) == want_components
    # scatter into DIFFERENT destination blocks of a fresh pool
    dst = [0, 6, 2]
    fresh = paged.alloc_kv_pool(cfg, 8, 4, kv_cache_dtype=kv_cache_dtype)
    payloads = [tuple(a[i] for a in payload) for i in range(len(src))]
    out = paged.restore_blocks_from_host(
        fresh[0], fresh[1], payloads, dst,
        k_scale=fresh[2], v_scale=fresh[3],
    )
    back = paged.gather_blocks_host(
        out[0], out[1], dst,
        k_scale=out[2] if len(out) > 2 else None,
        v_scale=out[3] if len(out) > 2 else None,
    )
    for a, b in zip(payload, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_block_round_trip_bit_identical_fp():
    _round_trip_pools("auto")


def test_host_block_round_trip_bit_identical_int8_with_scales():
    _round_trip_pools("int8")


@pytest.mark.parametrize("kv_cache_dtype", ["auto", "int8"])
def test_stacked_restore_matches_per_block_restore(kv_cache_dtype):
    """restore_blocks_host_stacked (the streamed-handoff segment wire
    format: ONE coalesced buffer per component) must land bit-identical
    pool contents to the per-block-tuple restore path on the same
    payload."""
    from areal_tpu.models import paged

    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    rng = np.random.default_rng(11)
    pools = paged.alloc_kv_pool(cfg, 8, 4, kv_cache_dtype=kv_cache_dtype)
    k_pool, v_pool, k_scale, v_scale = pools
    filled = []
    for a in (k_pool, v_pool):
        if kv_cache_dtype == "int8":
            filled.append(jax.numpy.asarray(
                rng.integers(-127, 128, a.shape).astype(np.int8)
            ))
        else:
            filled.append(jax.numpy.asarray(
                rng.standard_normal(a.shape).astype(np.float32)
            ).astype(a.dtype))
    k_pool, v_pool = filled
    if kv_cache_dtype == "int8":
        k_scale = jax.numpy.asarray(
            rng.random(k_scale.shape).astype(np.float32)
        )
        v_scale = jax.numpy.asarray(
            rng.random(v_scale.shape).astype(np.float32)
        )
    src, dst = [5, 1, 3], [0, 6, 2]
    payload = paged.gather_blocks_host(
        k_pool, v_pool, src, k_scale=k_scale, v_scale=v_scale
    )
    fresh_a = paged.alloc_kv_pool(cfg, 8, 4, kv_cache_dtype=kv_cache_dtype)
    fresh_b = paged.alloc_kv_pool(cfg, 8, 4, kv_cache_dtype=kv_cache_dtype)
    per_block = [tuple(a[i] for a in payload) for i in range(len(src))]
    out_a = paged.restore_blocks_from_host(
        fresh_a[0], fresh_a[1], per_block, dst,
        k_scale=fresh_a[2], v_scale=fresh_a[3],
    )
    out_b = paged.restore_blocks_host_stacked(
        fresh_b[0], fresh_b[1], payload, dst,
        k_scale=fresh_b[2], v_scale=fresh_b[3],
    )
    for a, b in zip(out_a, out_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
