"""int8 serving weights: quant-format + negotiation correctness gates.

Weight quantization is STORAGE-ONLY: every projection dequantizes its
``{int8 weight, f32 per-output-channel scale}`` leaf at use, so the only
admissible error is per-element rounding at quantize time.  This file
pins, on CPU:

* the format itself: per-output-channel round-trip error bounded by
  half a quantization step; the quantizable-path predicate (norms,
  biases, embeddings, the MoE router and the critic head stay model
  dtype); tree-transform structure invariants (idempotence, the
  abstract template matching the concrete tree, >= 1.8x byte shrink);
* the tier-1 serving smokes (one per integration, per the headroom
  budget): an int8 paged+prefix multi-turn replay with the measured
  greedy divergence pin vs the full-precision arm AND an int8 dense-
  mode arm (the acceptance matrix's dense leg), plus a quantized-tree
  swap mid-decode whose post-swap stream a fresh int8 engine must
  reproduce;
* the MANIFEST NEGOTIATION matrix, both ways, through the generation
  server's own code path: int8 server + quantized advertisement ->
  quantized restore; int8 server + old (no-quant) manifest / missing
  dir -> full-precision restore, quantized on arrival, one log line;
  quantized manifest + serving_weight_dtype="auto" -> full-precision
  tree preferred; arch mismatch on the quantized tree -> ONE readable
  error before the pause window (the validate_manifest extension);
* what the format buys: an int8 server's staged swap restores <= 55%
  of the full tree's bytes through the server's own restore path, and
  at a fixed HBM budget the freed weight bytes hold more full-context
  rows, more again composed with int8 KV.

Heavy parity arms (TP mesh, kv-int8 + weight-int8 composed) are
``slow``-marked from day one — ``pytest -m slow``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import quantize, transformer

from tests.engine.test_kv_quant import (
    _assert_only_near_ties_flip,
    _forced_replay,
    _replay,
)
from tests.engine.test_prefix_cache import (
    _req,
    make_engine,
    run_until_done,
)
from tests.helpers.divergence import lcp_divergence as _lcp_divergence

#: measured on the tiny-config multi-turn replay (same statistic and
#: shape as the kv-quant pin)
DIVERGENCE_BAR = 0.35


# -- the quant format itself --------------------------------------------------


def test_quantize_roundtrip_error_bound_per_output_channel():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((24, 16)).astype(np.float32) * 2.0)
    qw, scale = quantize.quantize_weight(w)
    assert qw.dtype == jnp.int8 and scale.shape == (16,)
    deq = np.asarray(quantize.dequant_weight(qw, scale, jnp.float32))
    err = np.abs(deq - np.asarray(w))
    # absmax scaling: error <= half a step PER OUTPUT CHANNEL
    assert (err <= np.asarray(scale)[None, :] * 0.5 + 1e-7).all()
    # stacked [L, E, D, F] leaves: scale keeps every leading axis
    w4 = jnp.asarray(rng.standard_normal((2, 3, 8, 5)).astype(np.float32))
    qw4, s4 = quantize.quantize_weight(w4)
    assert s4.shape == (2, 3, 5)
    deq4 = np.asarray(quantize.dequant_weight(qw4, s4, jnp.float32))
    assert (
        np.abs(deq4 - np.asarray(w4)) <= np.asarray(s4)[..., None, :] * 0.5 + 1e-7
    ).all()
    # all-zero channels dequantize to exact zeros
    qz, sz = quantize.quantize_weight(jnp.zeros((4, 3)))
    assert (np.asarray(quantize.dequant_weight(qz, sz, jnp.float32)) == 0).all()


def test_quantizable_path_predicate():
    yes = [
        ("layers", "attn", "q", "w"),
        ("layers", "attn", "o", "w"),
        ("layers", "mlp", "gate", "w"),
        ("layers", "mlp", "down", "w"),
        ("layers", "mlp", "experts", "gate"),
        ("layers", "mlp", "experts", "down"),
        ("lm_head", "w"),
    ]
    no = [
        ("embed", "weight"),
        ("pos_embed", "weight"),
        ("final_norm", "scale"),
        ("layers", "attn_norm", "scale"),
        ("layers", "attn", "q", "b"),
        ("layers", "attn", "q_norm", "scale"),
        ("layers", "mlp", "router", "w"),
        ("value_head", "w"),
        # quant-tree paths: idempotence depends on these being excluded
        ("layers", "attn", "q", "qw"),
        ("layers", "attn", "q", "scale"),
        ("layers", "mlp", "experts", "gate", "qw"),
    ]
    for kp in yes:
        assert quantize.quantizable(kp), kp
    for kp in no:
        assert not quantize.quantizable(kp), kp


def test_tree_transform_structure_and_bytes():
    from areal_tpu.models.config import tiny_config

    import jax.tree_util as jtu

    for moe in (False, True):
        cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
        if moe:
            import dataclasses

            cfg = dataclasses.replace(
                cfg, n_experts=4, n_experts_per_tok=2,
                moe_intermediate_dim=cfg.intermediate_dim,
            )
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        q = quantize.quantize_param_tree(params)
        assert quantize.is_quantized_tree(q)
        assert not quantize.is_quantized_tree(params)
        assert quantize.quantized_leaf_count(q) > 0
        # abstract template matches the concrete tree, from BOTH inputs
        assert jtu.tree_structure(
            quantize.quant_tree_struct(params)
        ) == jtu.tree_structure(q)
        assert jtu.tree_structure(
            quantize.quant_tree_struct(q)
        ) == jtu.tree_structure(q)
        # idempotent
        assert jtu.tree_structure(
            quantize.quantize_param_tree(q)
        ) == jtu.tree_structure(q)
        # the headline claim: tiny configs are f32, so >= 1.8x easily
        assert quantize.tree_bytes(params) / quantize.tree_bytes(q) >= 1.8
        # norms/embeddings stayed full precision
        assert q["embed"]["weight"].dtype == params["embed"]["weight"].dtype
        if moe:
            assert "qw" in q["layers"]["mlp"]["experts"]["gate"]
            assert q["layers"]["mlp"]["router"]["w"].dtype != jnp.int8


def test_serving_pspecs_cover_quant_leaves():
    """Every quant-tree leaf gets a pspec whose rank fits the leaf (the
    scan/sharding machinery relies on this for both TP and EP trees)."""
    import dataclasses

    import jax.tree_util as jtu

    from areal_tpu.models.config import tiny_config

    cfg = dataclasses.replace(
        tiny_config(vocab_size=64, max_position_embeddings=512),
        n_experts=4, n_experts_per_tok=2,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    q = quantize.quantize_param_tree(params)
    for fn in (transformer.param_pspecs, transformer.serving_param_pspecs):
        specs = fn(cfg, q)
        assert jtu.tree_structure(specs) == jtu.tree_structure(q)

        def chk(path, leaf, spec):
            assert spec is None or len(spec) <= len(leaf.shape), (
                path, spec, leaf.shape,
            )

        jtu.tree_map_with_path(chk, q, specs)
    # EP serving: expert scale leaves shard the expert axis
    sspecs = transformer.serving_param_pspecs(cfg, q)
    assert sspecs["layers"]["mlp"]["experts"]["gate"]["scale"][1] == "expert"


# -- tier-1 serving smokes ----------------------------------------------------


#: |fp logprob - int8-weight logprob| on identical prefixes.  Measured on
#: the tiny-config replay: 0.011 at worst, flips at gaps up to 0.015 —
#: per-output-channel absmax/127 rounding of every matmul weight.
WEIGHT_INT8_LOGP_TOL = 0.04


def test_int8_weight_divergence_pin_paged_prefix_and_dense():
    """THE tier-1 quantized decode smoke, pinned at the LOGIT level (see
    test_kv_quant.test_int8_divergence_pin_on_multi_turn_replay for why
    not greedy streams): int8 serving weights on the paged multi-turn
    replay keep the fp arm's log-probabilities
    to rounding on teacher-forced prefixes, flips only at near-ties
    (folded into the engine's weight_quant counters), and the DENSE
    int8 arm passes the same pin — the acceptance matrix's dense leg."""
    # prefix cache off in both arms, as in the kv-quant pin (see there)
    fp, *_ = make_engine(prefix_cache=False)
    q, *_ = make_engine(serving_weight_dtype="int8", prefix_cache=False)
    fp.park_ttl_steps = q.park_ttl_steps = 0
    rows = _forced_replay(fp, q)
    n_div = _assert_only_near_ties_flip(rows, WEIGHT_INT8_LOGP_TOL)
    q.note_weight_divergence_check(len(rows), n_div)
    st = q.weight_quant_stats()
    assert st["quantized"] == 1 and st["storage_bits"] == 8
    assert st["quantized_leaves"] > 0
    assert st["divergence_checks_total"] == len(rows)
    assert st["divergence_diverged_total"] == n_div
    # resident tree really is ~half the bytes
    fp_bytes = fp.weight_quant_stats()["param_bytes"]
    assert fp_bytes / st["param_bytes"] >= 1.8
    # dense-mode int8 arm: same engine knob, dense cache path
    fpd, *_ = make_engine(cache_mode="dense")
    qd, *_ = make_engine(cache_mode="dense", serving_weight_dtype="int8")
    fpd.park_ttl_steps = qd.park_ttl_steps = 0
    _assert_only_near_ties_flip(
        _forced_replay(fpd, qd, turns=1), WEIGHT_INT8_LOGP_TOL
    )


def test_auto_arm_token_identical_to_dense():
    """Acceptance pin: serving_weight_dtype='auto' (the default) must be
    token-identical to the dense engine — the weight-quant plumbing (the
    format-agnostic weight accessor on every projection) cannot perturb
    the unquantized serving path."""
    paged_eng, *_ = make_engine(serving_weight_dtype="auto")
    dense_eng, *_ = make_engine(cache_mode="dense")
    paged_eng.park_ttl_steps = dense_eng.park_ttl_steps = 0
    assert _replay(paged_eng) == _replay(dense_eng)
    st = paged_eng.weight_quant_stats()
    assert st["quantized"] == 0 and st["quantized_leaves"] == 0


def test_quantized_swap_mid_decode_post_swap_parity():
    """A quantized-tree weight swap mid-decode keeps the PR-8 swap
    invariants: the prefix cache flushes, in-flight rows recompute, and
    the post-swap stream matches a FRESH int8 engine running the new
    weights from scratch."""
    eng, cfg, _ = make_engine(serving_weight_dtype="int8")
    rng = np.random.default_rng(3)
    conv = list(rng.integers(6, 60, (20,)))
    eng.submit(_req("pre", conv, 8))
    for _ in range(3):
        eng.step()  # mid-decode
    params1 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    # the tree arrives in the engine's resident format, as the server's
    # negotiation guarantees
    eng.update_weights(eng.prepare_weights(params1), version=1)
    eng.step()  # the apply happens at the next engine step
    run_until_done(eng)
    eng.drain_results()
    assert eng.version == 1
    assert quantize.is_quantized_tree(eng.params)
    eng.submit(_req("post", conv, 8))
    run_until_done(eng)
    got = eng.drain_results()["post"]
    fresh, *_ = make_engine(params=params1, serving_weight_dtype="int8")
    fresh.submit(_req("post", conv, 8))
    run_until_done(fresh)
    assert got.output_ids == fresh.drain_results()["post"].output_ids


# -- manifest negotiation matrix (both ways) ----------------------------------


from areal_tpu.system.generation_server import (  # noqa: E402
    GenerationServerWorker as _GSW,
)


class _StubServer:
    """The generation server's negotiation/restore methods, detached
    from the worker's ZMQ/process machinery: exactly self.config,
    self.logger and self.engine — what _negotiate_weight_format /
    _load_update_params read."""

    _negotiate_weight_format = _GSW._negotiate_weight_format
    _load_update_params = _GSW._load_update_params

    def __init__(self, engine, serving_weight_dtype):
        import types

        from areal_tpu.base import logging_

        self.engine = engine
        self.config = types.SimpleNamespace(
            serving_weight_dtype=serving_weight_dtype,
            stage_chunk_bytes=1 << 20,
        )
        self.logger = logging_.getLogger("test-negotiation")

    def negotiate(self, path, manifest):
        return self._negotiate_weight_format(path, manifest)

    def load(self, payload, staged=True):
        return self._load_update_params(payload, staged)


def _publish(params, pub, with_quant=True, version=1):
    """Publish like model_worker does: full tree + (optionally) the int8
    sibling, manifest advertising what was actually written."""
    from areal_tpu.engine import checkpoint

    snap = os.path.join(pub, f"v{version}")
    checkpoint.save_params(params, snap)
    serving_quant = None
    if with_quant:
        qpath = checkpoint.quant_snapshot_path(snap)
        qavals = checkpoint.save_quantized_params(params, qpath)
        serving_quant = {
            "int8": checkpoint.quant_manifest_entry(qavals, qpath)
        }
    checkpoint.write_manifest(
        jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), params
        ),
        snap,
        version=version,
        serving_quant=serving_quant,
    )
    return snap


def test_negotiation_matrix_no_combination_crashes(tmp_path):
    """The publisher/server format matrix, through the server's own
    restore path: every combination restores a servable tree in the
    engine's resident format; the fallbacks log, never crash."""
    from areal_tpu.engine import checkpoint

    eng_q, cfg, params = make_engine(serving_weight_dtype="int8")
    eng_a, *_ = make_engine(serving_weight_dtype="auto")
    params1 = transformer.init_params(cfg, jax.random.PRNGKey(9))

    snap_q = _publish(params1, str(tmp_path), with_quant=True, version=1)
    snap_f = _publish(params1, str(tmp_path), with_quant=False, version=2)
    payload_q = {"path": snap_q, "format": "params", "version": 1}
    payload_f = {"path": snap_f, "format": "params", "version": 2}

    # new server (int8) + quantized publisher -> the advertised tree
    srv = _StubServer(eng_q, "int8")
    fmt, rpath, leaves = srv.negotiate(
        snap_q, checkpoint.read_manifest(snap_q)
    )
    assert fmt == "int8" and rpath.endswith("v1-int8") and leaves
    for staged in (True, False):
        restored = srv.load(payload_q, staged=staged)
        assert quantize.is_quantized_tree(restored)
        # bit-identical to quantizing the published params locally
        want = quantize.quantize_param_tree(params1)
        got_leaf = restored["layers"]["attn"]["q"]["qw"]
        np.testing.assert_array_equal(
            np.asarray(got_leaf),
            np.asarray(want["layers"]["attn"]["q"]["qw"]),
        )

    # new server (int8) + OLD publisher (no quant tree) -> full restore,
    # quantized on arrival
    fmt, rpath, leaves = srv.negotiate(
        snap_f, checkpoint.read_manifest(snap_f)
    )
    assert fmt == "full" and rpath == snap_f and leaves is None
    restored = srv.load(payload_f, staged=True)
    assert quantize.is_quantized_tree(restored)

    # manifest-less snapshot (pre-manifest publisher) -> same fallback
    os.remove(os.path.join(snap_f, checkpoint.MANIFEST_NAME))
    assert srv.negotiate(snap_f, None)[0] == "full"
    restored = srv.load(payload_f, staged=True)
    assert quantize.is_quantized_tree(restored)

    # advertised dir GONE (GC race) -> fallback, not a crash
    manifest = checkpoint.read_manifest(snap_q)
    import shutil

    shutil.rmtree(checkpoint.quant_snapshot_path(snap_q))
    assert srv.negotiate(snap_q, manifest)[0] == "full"

    # quantized manifest + serving_weight_dtype='auto' -> full-precision
    # tree PREFERRED (today's behavior, bit for bit)
    srv_a = _StubServer(eng_a, "auto")
    fmt, rpath, _ = srv_a.negotiate(
        snap_q, checkpoint.read_manifest(snap_q)
    )
    assert fmt == "full" and rpath == snap_q
    restored = srv_a.load(payload_q, staged=True)
    assert not quantize.is_quantized_tree(restored)


def test_arch_mismatch_on_quant_tree_fails_readably(tmp_path):
    """Arch skew on the QUANTIZED tree fails as one readable error at
    stage time — before the fleet's pause window — via the
    validate_manifest extension (shape + int/float dtype-class)."""
    import dataclasses

    from areal_tpu.engine import checkpoint
    from areal_tpu.models.config import tiny_config

    eng_q, cfg, _ = make_engine(serving_weight_dtype="int8")
    other_cfg = dataclasses.replace(cfg, intermediate_dim=cfg.intermediate_dim * 2)
    other = transformer.init_params(other_cfg, jax.random.PRNGKey(5))
    snap = _publish(other, str(tmp_path), with_quant=True, version=3)
    srv = _StubServer(eng_q, "int8")
    with pytest.raises(RuntimeError, match="does not match"):
        srv.load({"path": snap, "format": "params", "version": 3},
                 staged=True)
    # the dtype-class extension: int8 storage never casts to/from float
    template = quantize.quant_tree_struct(
        transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    full_manifest = checkpoint.read_manifest(
        _publish(
            transformer.init_params(cfg, jax.random.PRNGKey(0)),
            str(tmp_path), with_quant=False, version=4,
        )
    )
    problems = checkpoint.validate_manifest(template, full_manifest)
    assert problems and any(
        "dtype-class" in p or "missing" in p for p in problems
    )


def test_freed_weight_bytes_buy_pool_rows_at_fixed_hbm():
    """The capacity the two quantizations buy together, counted from the
    engines' own byte accounts at one fixed budget (full-precision
    weights + the fp pool): int8 weights free bytes that hold strictly
    more full-context rows, and int8 KV on top holds at least as many
    again."""
    # rows of 64 tokens: the tiny model's weights are worth a few of them
    fp, *_ = make_engine(kv_cache_len=64)
    wq, *_ = make_engine(kv_cache_len=64, serving_weight_dtype="int8")
    kvq, *_ = make_engine(kv_cache_len=64, kv_cache_dtype="int8")
    w_fp = fp.weight_quant_stats()["param_bytes"]
    w_q = wq.weight_quant_stats()["param_bytes"]
    budget = w_fp + fp._pool_block_bytes() * fp.n_blocks

    def rows(weight_bytes, block_bytes):
        return (budget - weight_bytes) // block_bytes // fp.blocks_per_row

    base = rows(w_fp, fp._pool_block_bytes())
    assert base == fp.n_blocks // fp.blocks_per_row
    assert rows(w_q, wq._pool_block_bytes()) > base
    assert rows(w_q, kvq._pool_block_bytes()) >= rows(
        w_q, wq._pool_block_bytes()
    )


# -- heavy parity arms (slow-marked from day one) -----------------------------


@pytest.mark.slow
def test_int8_weight_tp_mesh_parity():
    """int8 serving weights under a 2-way TP mesh (qw/scale leaves shard
    via the extended pspecs): token-identical to the single-chip int8
    engine."""
    from areal_tpu.base.topology import MeshSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices (CPU mesh via conftest XLA flags)")
    single, cfg, params = make_engine(serving_weight_dtype="int8")
    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    tp, *_ = make_engine(
        serving_weight_dtype="int8", mesh=mesh, params=params
    )
    rng = np.random.default_rng(1)
    conv = list(rng.integers(6, 60, (24,)))
    outs = {}
    for name, e in (("single", single), ("mesh", tp)):
        e.submit(_req(name, conv, 10))
        run_until_done(e, max_steps=3000)
        outs[name] = e.drain_results()[name].output_ids
    assert outs["mesh"] == outs["single"]
    # the mesh engine's resident tree is actually sharded quant leaves
    qw = tp.params["layers"]["attn"]["q"]["qw"]
    assert qw.dtype == jnp.int8
    shard = next(iter(qw.addressable_shards))
    assert shard.data.shape != qw.shape


@pytest.mark.slow
def test_int8_weight_moe_ep_parity():
    """int8 expert stacks under a 2-way EP mesh: each shard dequantizes
    its resident [E/ep, D, F] int8 slice outside the shard_map (no
    gather), and the greedy stream matches the single-chip int8 MoE
    engine token for token."""
    import dataclasses

    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.models.config import tiny_config

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices (CPU mesh via conftest XLA flags)")
    cfg = dataclasses.replace(
        tiny_config(vocab_size=128, max_position_embeddings=256),
        n_experts=4, n_experts_per_tok=2,
    )
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(
        max_batch=2, kv_cache_len=128, chunk_size=4,
        cache_mode="paged", page_size=16, prefill_chunk_tokens=16,
        serving_weight_dtype="int8",
    )
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from areal_tpu.engine.sampling import SamplingParams

    single = ContinuousBatchingEngine(
        cfg, params, sampling=SamplingParams(greedy=True), **kw
    )
    mesh = MeshSpec(expert=2).make_mesh(jax.devices()[:2])
    ep = ContinuousBatchingEngine(
        cfg, params, mesh=mesh, sampling=SamplingParams(greedy=True), **kw
    )
    # the expert qw really is sharded int8 (E/ep per chip, never
    # silently replicated), and its scale shards the same axis
    qw = ep.params["layers"]["mlp"]["experts"]["gate"]["qw"]
    sc = ep.params["layers"]["mlp"]["experts"]["gate"]["scale"]
    assert qw.dtype == jnp.int8
    assert qw.sharding.shard_shape(qw.shape)[1] == qw.shape[1] // 2
    assert sc.sharding.shard_shape(sc.shape)[1] == sc.shape[1] // 2
    rng = np.random.default_rng(4)
    conv = list(rng.integers(6, 100, (20,)))
    outs = {}
    for name, e in (("single", single), ("ep", ep)):
        e.submit(_req(name, conv, 8))
        run_until_done(e, max_steps=3000)
        outs[name] = e.drain_results()[name].output_ids
    assert outs["ep"] == outs["single"]


@pytest.mark.slow
def test_int8_weights_and_int8_kv_composed_sweep():
    """Both quantizations together (the capacity configuration of
    test_freed_weight_bytes_buy_pool_rows_at_fixed_hbm): multi-turn
    replay divergence vs the all-fp arm stays under the bar, and both
    storage families report quantized."""
    fp, *_ = make_engine()
    both, *_ = make_engine(
        serving_weight_dtype="int8", kv_cache_dtype="int8"
    )
    fp.park_ttl_steps = both.park_ttl_steps = 0
    rate, n_div = _lcp_divergence(
        _replay(fp, n_sessions=4, turns=3),
        _replay(both, n_sessions=4, turns=3),
    )
    both.note_weight_divergence_check(8, n_div)
    assert rate <= DIVERGENCE_BAR, rate
    assert both.weight_quant_stats()["quantized"] == 1
    assert both.kv_quant_stats()["quantized"] == 1


def test_staged_swap_bytes_and_residency():
    """An int8 engine stages the advertised quantized tree through the
    server's restore path — restored bytes <= ~55% of the full tree's —
    and the committed tree serves (post-swap replay equals a fresh
    engine on the published params)."""
    import tempfile

    from areal_tpu.engine import checkpoint

    eng, cfg, _ = make_engine(serving_weight_dtype="int8")
    params1 = transformer.init_params(cfg, jax.random.PRNGKey(11))
    with tempfile.TemporaryDirectory() as pub:
        snap = _publish(params1, pub, with_quant=True, version=7)
        srv = _StubServer(eng, "int8")
        restored = srv.load(
            {"path": snap, "format": "params", "version": 7}, staged=True
        )
        full_bytes = quantize.tree_bytes(
            transformer.init_params(cfg, jax.random.PRNGKey(11))
        )
        assert quantize.tree_bytes(restored) <= 0.55 * full_bytes
        eng.stage_weights(restored, 7)
        eng.commit_staged(expected_version=7)
        eng.step()
        assert eng.version == 7
        conv = list(np.random.default_rng(2).integers(6, 60, (20,)))
        eng.submit(_req("post", conv, 8))
        run_until_done(eng)
        got = eng.drain_results()["post"]
        fresh, *_ = make_engine(
            params=params1, serving_weight_dtype="int8"
        )
        fresh.submit(_req("post", conv, 8))
        run_until_done(fresh)
        assert got.output_ids == fresh.drain_results()["post"].output_ids
