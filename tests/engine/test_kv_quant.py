"""int8 KV cache on the paged serving path: quant-format correctness
gates.

Quantization is STORAGE-ONLY: every read dequantizes inline next to the
block gather, so the only admissible error is per-element rounding at
insert.  This file pins, on CPU:

* the format itself: quantize->dequantize round-trip error bounded by
  half a quantization step per (token, head); all-zero vectors exact;
* engine invariants that must carry scales with bytes: COW tail copies,
  host-tier spill -> restore bit-identity of the int8 blocks AND their
  scales, weight-swap flushes dropping scale-bearing host payloads with
  the blocks;
* the serving smokes tier-1 keeps (one per integration, per the
  headroom budget): a quant paged decode wave pinned to the fp arm at
  the logit level (teacher-forced log-probabilities; greedy flips only
  at near-ties), and a spilled-prefix swap-in arm over an int8 pool;
* ``kv_cache_dtype="auto"`` parity: the quantization plumbing must
  leave the unquantized path token-identical to the dense engine (the
  acceptance criterion's pre-PR-behavior pin);
* what the quantized pool buys at an EQUAL byte budget: >= 1.8x paged
  blocks per HBM byte, more full-context rows, and a higher
  ``cached_token_frac`` on a multi-turn replay under cache pressure.

Heavy parity arms (TP mesh, the host-tier sweep at pressure) are
``slow``-marked from day one — run ``pytest -m slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import paged

from tests.helpers.divergence import lcp_divergence as _lcp_divergence
from tests.engine.test_prefix_cache import (
    _req,
    make_engine,
    run_until_done,
)

#: measured on the tiny-config multi-turn replay (see
#: test_int8_divergence_pin): one request in ~5 flips a tail token.  The
#: bar is asserted, not eyeballed.
DIVERGENCE_BAR = 0.35


# -- the quant format itself --------------------------------------------------


def test_quantize_roundtrip_error_bounds_per_head():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(
        rng.standard_normal((5, 3, 16)).astype(np.float32) * 3.0
    )
    q, s = quant = paged.quantize_kv(vals)
    assert q.dtype == jnp.int8 and s.shape == (5, 3)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(np.asarray(vals) - deq)
    # absmax scaling: error <= half a quantization step, PER (row, head)
    step = np.asarray(s)
    assert (err <= step[..., None] * 0.5 + 1e-7).all()
    # the absmax element itself is exact up to the step rounding
    assert (np.abs(deq).max(-1) > 0).all()


def test_quantize_zero_vectors_are_exact():
    q, s = paged.quantize_kv(jnp.zeros((2, 4, 8)))
    assert (np.asarray(q) == 0).all() and (np.asarray(s) == 0).all()
    assert (np.asarray(q, np.float32) * np.asarray(s)[..., None] == 0).all()


def test_alloc_kv_pool_variants():
    from areal_tpu.models.config import tiny_config

    cfg = tiny_config()
    k, v, ks, vs = paged.alloc_kv_pool(cfg, 6, 16, kv_cache_dtype="auto")
    assert ks is None and vs is None and k.dtype == jnp.dtype(cfg.dtype)
    k, v, ks, vs = paged.alloc_kv_pool(cfg, 6, 16, kv_cache_dtype="int8")
    assert k.dtype == jnp.int8 and ks.dtype == jnp.float32
    assert ks.shape == k.shape[:-1]
    with pytest.raises(ValueError):
        paged.alloc_kv_pool(cfg, 6, 16, kv_cache_dtype="fp8")


# -- engine invariants: scales travel with bytes ------------------------------


def _fill_some_blocks(eng, seed=0, max_new=8):
    rng = np.random.default_rng(seed)
    conv = list(rng.integers(6, 60, (24,)))
    eng.submit(_req("fill", conv, max_new))
    run_until_done(eng)
    eng.drain_results()


def test_cow_copy_preserves_scales():
    eng, *_ = make_engine(kv_cache_dtype="int8")
    _fill_some_blocks(eng)
    used = [b for b in range(eng.n_blocks) if eng._pages._ref[b] > 0]
    free = [b for b in range(eng.n_blocks) if eng._pages._ref[b] == 0]
    src, dst = used[0], free[0]
    eng._copy_pages(eng._pages, [src], [dst])
    for pool in (eng.k_pool, eng.v_pool, eng.k_scale, eng.v_scale):
        np.testing.assert_array_equal(
            np.asarray(pool[:, dst]), np.asarray(pool[:, src])
        )
    # the copied block's scales are non-trivial (the prompt wrote KV)
    assert np.asarray(eng.k_scale[:, src]).max() > 0


def _pressure_int8_engine(**kw):
    defaults = dict(
        kv_cache_dtype="int8",
        kv_pool_tokens=160,
        prefix_cache_capacity_frac=0.25,
        prefix_cache_host_bytes=1 << 24,
    )
    defaults.update(kw)
    eng, cfg, params = make_engine(**defaults)
    eng.park_ttl_steps = 0
    return eng, cfg, params


def test_spill_restore_bit_identity_of_int8_blocks():
    """A spilled int8 block must swap back in BIT-identical: same int8
    bytes, same scales — no requantization round trip."""
    eng, *_ = _pressure_int8_engine()
    _fill_some_blocks(eng)
    eng.step()
    eng.step()  # TTL-release the parked row; cache refs remain
    cache = eng._prefix_cache
    held = [b for b in range(eng.n_blocks) if eng._pages._ref[b] > 0]
    assert held, "prompt KV should be cache-resident"
    # snapshot the cached blocks' device contents, then force a spill
    before = {
        b: [np.asarray(p[:, b]).copy() for p in eng._pool_arrays()]
        for b in held
    }
    cache.evict(cache.blocks_held)
    spilled = [
        n for n in _walk_nodes(cache) if n.spilled and n.host_kv
    ]
    assert spilled
    # host payload carries 4 components (int8 k/v + f32 scales), and the
    # per-block bytes match the engine's derived block_bytes EXACTLY
    for node in spilled:
        assert len(node.host_kv) == 4
        assert (
            sum(int(a.nbytes) for a in node.host_kv) == cache.block_bytes
        )
    # swap back in via a fresh match on the same prefix
    rng = np.random.default_rng(0)
    conv = list(rng.integers(6, 60, (24,)))
    eng.submit(_req("again", conv, 8))
    run_until_done(eng, max_steps=3000)
    eng.drain_results()
    st = eng.prefix_cache_stats()
    assert st["restored_blocks_total"] > 0
    # the restored nodes' NEW blocks hold the original bytes + scales
    restored = [
        n for n in _walk_nodes(cache) if not n.spilled and n.block >= 0
    ]
    assert restored
    checked = 0
    for node in restored:
        for old_block, arrs in before.items():
            if np.array_equal(
                arrs[0], np.asarray(eng.k_pool[:, node.block])
            ):
                for p, a in zip(eng._pool_arrays(), arrs):
                    np.testing.assert_array_equal(
                        np.asarray(p[:, node.block]), a
                    )
                checked += 1
                break
    assert checked > 0, "no restored block matched a pre-spill snapshot"


def _walk_nodes(cache):
    stack = list(cache._root.children.values())
    while stack:
        n = stack.pop()
        stack.extend(n.children.values())
        yield n


def test_weight_swap_flush_drops_scales_with_blocks():
    """After update_weights BOTH tiers are empty — including the
    scale-bearing host payloads — and the next request matches a fresh
    engine under the new weights."""
    from areal_tpu.models import transformer

    eng, cfg, _ = _pressure_int8_engine()
    _fill_some_blocks(eng)
    eng._prefix_cache.evict(eng.prefix_cache_stats()["blocks_held"])
    assert eng.prefix_cache_stats()["host_blocks_held"] > 0
    assert any(n.host_kv for n in _walk_nodes(eng._prefix_cache))

    params1 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    eng.update_weights(params1, version=1)
    eng.step()
    st = eng.prefix_cache_stats()
    assert st["blocks_held"] == 0
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0
    assert not any(n.host_kv for n in _walk_nodes(eng._prefix_cache))

    conv = list(np.random.default_rng(3).integers(6, 60, (20,)))
    eng.submit(_req("post-swap", conv, 8))
    run_until_done(eng)
    got = eng.drain_results()["post-swap"]
    fresh, *_ = make_engine(params=params1, kv_cache_dtype="int8")
    fresh.submit(_req("fresh", conv, 8))
    run_until_done(fresh)
    assert got.output_ids == fresh.drain_results()["fresh"].output_ids


# -- tier-1 serving smokes ----------------------------------------------------


def _replay(eng, n_sessions=3, turns=2, seed=0, max_new=8, user_len=6):
    rng = np.random.default_rng(seed)
    convs = [list(rng.integers(6, 60, (24,))) for _ in range(n_sessions)]
    streams = {}
    for t in range(turns):
        for s in range(n_sessions):
            qid = f"s{s}t{t}"
            eng.submit(_req(qid, convs[s], max_new))
            run_until_done(eng, max_steps=3000)
            out = eng.drain_results()[qid]
            streams[qid] = list(out.output_ids)
            convs[s] = (
                convs[s]
                + list(out.output_ids)
                + list(rng.integers(6, 60, (user_len,)))
            )
    return streams


def _forced_replay(
    fp, q, n_sessions=3, turns=2, seed=0, max_new=8, user_len=6
):
    """The multi-turn replay TEACHER-FORCED: both engines answer the same
    conversation every turn (it grows by the fp arm's tokens), so one
    flipped token cannot rewrite every later prompt.  Per request:
    ``(n_tokens, agreed_prefix, max |dlogp| over the prefix, logprob gap
    at the first flip or None)`` — the gap is fp's logprob of ITS token
    minus the quantized arm's logprob of its own."""
    rng = np.random.default_rng(seed)
    convs = [list(rng.integers(6, 60, (24,))) for _ in range(n_sessions)]
    rows = []
    for t in range(turns):
        for s in range(n_sessions):
            qid = f"s{s}t{t}"
            outs = []
            for eng in (fp, q):
                eng.submit(_req(qid, convs[s], max_new))
                run_until_done(eng, max_steps=3000)
                outs.append(eng.drain_results()[qid])
            a, b = outs
            ta, tb = list(a.output_ids), list(b.output_ids)
            n = min(len(ta), len(tb))
            k = next((i for i in range(n) if ta[i] != tb[i]), n)
            la = np.asarray(a.output_logprobs, np.float64)
            lb = np.asarray(b.output_logprobs, np.float64)
            rows.append(
                (
                    n,
                    k,
                    float(np.abs(la[:k] - lb[:k]).max()) if k else 0.0,
                    float(la[k] - lb[k]) if k < n else None,
                )
            )
            convs[s] = (
                convs[s] + ta + list(rng.integers(6, 60, (user_len,)))
            )
    return rows


def _assert_only_near_ties_flip(rows, logp_tol):
    """The quantized arm's error is storage rounding and nothing else:
    wherever the two arms emit the same tokens their log-probabilities
    agree to ``logp_tol``, and a greedy flip happens only where the two
    candidates' log-probabilities were within that same error (a
    near-tie of the tiny random model, which ANY rounding change flips
    — a different jax release's reduction order included).  Returns the
    number of requests that flipped."""
    assert any(k > 0 for _, k, _, _ in rows), rows
    for n, k, prefix_err, gap in rows:
        assert prefix_err <= logp_tol, rows
        assert gap is None or abs(gap) <= logp_tol, rows
    return sum(1 for n, k, _, _ in rows if k < n)


#: |fp logprob - int8-KV logprob| on identical prefixes.  Measured on the
#: tiny-config replay: 0.005 at worst (logprobs near -4.1), the size of
#: absmax/127 rounding of K and V; flips sat at gaps of 1e-4 and 1.6e-3.
KV_INT8_LOGP_TOL = 0.02


def test_int8_divergence_pin_on_multi_turn_replay():
    """The quant paged decode smoke + the quality pin, at the LOGIT level:
    greedy streams of a tiny random model flip at near-ties under any
    rounding change (the free-running greedy-stream statistic moved from
    ~0.2 to 0.46 on a jax upgrade with the int8 path untouched), so the
    pin holds the int8 arm's log-probabilities to the fp arm's on
    teacher-forced prefixes and allows a flip only at a near-tie.  The
    flips still land in the engine's kv_quant divergence counters."""
    # prefix cache off in both arms: this is a numerics pin, and a cached
    # prefix's KV was computed under another chunk layout.  (The cache +
    # int8 integration stays covered by the swap-in smokes below.)
    fp, *_ = make_engine(prefix_cache=False)
    q, *_ = make_engine(kv_cache_dtype="int8", prefix_cache=False)
    fp.park_ttl_steps = q.park_ttl_steps = 0
    rows = _forced_replay(fp, q)
    n_div = _assert_only_near_ties_flip(rows, KV_INT8_LOGP_TOL)
    q.note_kv_divergence_check(len(rows), n_div)
    st = q.kv_quant_stats()
    assert st["quantized"] == 1 and st["storage_bits"] == 8
    assert st["divergence_checks_total"] == len(rows)
    assert st["divergence_diverged_total"] == n_div
    # storage really is quantized + scales: half-or-less block bytes
    assert q._pool_block_bytes() < fp._pool_block_bytes() / 1.8


def test_int8_spilled_prefix_swap_in_smoke():
    """The one tier-1 host-tier arm over an int8 pool: pressure replay
    spills and restores quantized blocks, token streams stay within the
    divergence bar of an UNPRESSURED fp engine, and both tiers drain to
    zero with the pool pristine."""
    eng, *_ = _pressure_int8_engine()
    streams = _replay(eng)
    st = eng.prefix_cache_stats()
    assert st["spilled_blocks_total"] > 0, st
    assert st["restored_blocks_total"] > 0, st

    ref, *_ = make_engine(kv_pool_tokens=2048)
    ref.park_ttl_steps = 0
    rate, _ = _lcp_divergence(_replay(ref), streams)
    assert rate <= DIVERGENCE_BAR, rate

    eng.step()
    eng.step()
    eng._prefix_cache.flush()
    st = eng.prefix_cache_stats()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0


def test_auto_arm_token_identical_to_dense():
    """Acceptance pin: kv_cache_dtype='auto' (the default) must be
    token-identical to the dense engine — the quantization plumbing
    (optional scales through every pool path) cannot perturb the
    unquantized serving path."""
    paged_eng, *_ = make_engine(kv_cache_dtype="auto")
    dense_eng, *_ = make_engine(cache_mode="dense")
    paged_eng.park_ttl_steps = dense_eng.park_ttl_steps = 0
    assert _replay(paged_eng) == _replay(dense_eng)
    st = paged_eng.kv_quant_stats()
    assert st["quantized"] == 0 and st["quantized_blocks_held"] == 0


def test_dense_mode_rejects_int8_with_warning():
    eng, *_ = make_engine(cache_mode="dense", kv_cache_dtype="int8")
    assert not eng._kv_quant and eng.kv_cache_dtype == "auto"


def test_int8_pool_buys_rows_and_cache_hits_at_equal_hbm():
    """What int8 KV storage is for, counted at an EQUAL pool byte budget:
    >= 1.8x blocks a byte (from the layout arithmetic the allocator and
    the HBM ledger share), strictly more full-context rows, and, on the
    multi-turn replay with the radix cache under pressure, a strictly
    higher share of prompt tokens served from cache."""
    # one full-context row of pool and a cache share (8 blocks) that
    # the replay's second turn (3 sessions x 5 blocks) overflows
    fp, cfg, _ = make_engine(
        kv_pool_tokens=256, prefix_cache_capacity_frac=0.25
    )
    page = fp.page_size
    fp_block = sum(paged.kv_pool_layout_bytes(cfg, 1, page))
    q_block = sum(paged.kv_pool_layout_bytes(cfg, 1, page, "int8"))
    assert fp_block == fp._pool_block_bytes()
    assert fp_block / q_block >= 1.8
    budget = fp_block * fp.n_blocks
    q, *_ = make_engine(
        kv_cache_dtype="int8",
        kv_pool_tokens=(budget // q_block) * page,
        prefix_cache_capacity_frac=0.25,
    )
    assert q_block == q._pool_block_bytes()
    assert q._pool_block_bytes() * q.n_blocks <= budget
    assert (
        q.n_blocks // q.blocks_per_row > fp.n_blocks // fp.blocks_per_row
    )
    fp.park_ttl_steps = q.park_ttl_steps = 0
    cached = {}
    for name, eng in (("auto", fp), ("int8", q)):
        _replay(eng)
        cached[name] = eng.prefix_cache_stats()["cached_tokens_total"]
    # both arms were sent the same prompts, so the totals compare as shares
    assert cached["int8"] > cached["auto"], cached


# -- heavy parity arms (slow-marked from day one) -----------------------------


@pytest.mark.slow
def test_int8_tp_mesh_parity():
    """int8 pools under a 2-way TP mesh (scale pools shard the kv-head
    axis beside the data pools): token-identical to the single-chip
    int8 engine."""
    from areal_tpu.base.topology import MeshSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices (CPU mesh via conftest XLA flags)")
    single, cfg, params = make_engine(kv_cache_dtype="int8")
    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    tp, *_ = make_engine(kv_cache_dtype="int8", mesh=mesh, params=params)
    rng = np.random.default_rng(1)
    conv = list(rng.integers(6, 60, (24,)))
    outs = {}
    for name, e in (("single", single), ("mesh", tp)):
        e.submit(_req(name, conv, 10))
        run_until_done(e, max_steps=3000)
        outs[name] = e.drain_results()[name].output_ids
    assert outs["mesh"] == outs["single"]


@pytest.mark.slow
def test_int8_hier_pressure_sweep():
    """int8 + host tier at heavier pressure (more sessions/turns than
    the tier-1 smoke): spills, restores, divergence bar, zero leaks."""
    eng, *_ = _pressure_int8_engine()
    streams = _replay(eng, n_sessions=4, turns=3)
    st = eng.prefix_cache_stats()
    assert st["spilled_blocks_total"] > 0
    assert st["restored_blocks_total"] > 0
    ref, *_ = make_engine(kv_pool_tokens=4096)
    ref.park_ttl_steps = 0
    rate, _ = _lcp_divergence(_replay(ref, n_sessions=4, turns=3), streams)
    assert rate <= DIVERGENCE_BAR, rate
    eng.step()
    eng.step()
    eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    st = eng.prefix_cache_stats()
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0
