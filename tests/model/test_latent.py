"""Latent attention (MLA), leading dense layers and the group-limited
sigmoid router in a stack stated by kind (models/hybrid.py, the deepseek_v3
family) against the benchmark's plain reference, on the CPU at a tiny
size: 1 dense + 3 expert layers, 16 experts in 4 groups, top 3 of the best
2 groups, one shared expert; seeded weights, float32.  The reference
(benchmark/lib/reference_deepseek_v3.py) calls no model code: it is a
second implementation of the published equations, unabsorbed."""

import dataclasses
import json
import logging
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, moe, paged
from areal_tpu.models.hf import deepseek_v3
from areal_tpu.models.hf.registry import family_from_architecture
from benchmark.lib import reference_deepseek_v3 as ref

ARCH = "DeepseekV3ForCausalLM"
# value heads (6) are NOT as wide as query/key heads (8 + 4), so nothing
# here can lean on the two being equal, as they are in the published model
HF = dict(
    architectures=[ARCH], model_type="deepseek_v3", vocab_size=64,
    max_position_embeddings=256, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=4, num_nextn_predict_layers=1,
    num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
    n_routed_experts=16, ep_size=1, routed_scaling_factor=2.5,
    kv_lora_rank=24, q_lora_rank=16, qk_rope_head_dim=4, v_head_dim=6,
    qk_nope_head_dim=8, topk_method="noaux_tc", n_group=4, topk_group=2,
    num_experts_per_tok=3, moe_layer_freq=1, first_k_dense_replace=1,
    norm_topk_prob=True, scoring_func="sigmoid", hidden_act="silu",
    rms_norm_eps=1e-6, rope_theta=100000,
    rope_scaling=dict(
        beta_fast=32, beta_slow=1, factor=8, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=16, rope_type="yarn",
    ),
    attention_bias=False, tie_word_embeddings=False, torch_dtype="bfloat16",
)
FAMILY = family_from_architecture(ARCH)


def make_cfg(**over):
    return dataclasses.replace(FAMILY.config_from_hf(HF), dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def test_layer_plan_cuts_runs_by_mixer_and_mlp():
    plan = hybrid.layer_plan(make_cfg())
    # (each run a period of its own: nothing repeats a pattern of singles)
    assert all((r.every, r.strides) == (1, (1, 1, 1, 1)) for r in plan)
    assert hybrid.plan_periods(make_cfg()) == tuple((r,) for r in plan)
    assert [tuple(r)[:8] for r in plan] == [
        # (..., count, rope, number in its pool)
        ("latent", "dense", 0, 0, 0, 1, True, 0),
        ("latent", "experts", 1, 1, 0, 3, True, 1),
    ]
    cfg = make_cfg()
    assert cfg.is_latent and cfg.n_attn_layers == 4 and cfg.n_mamba_layers == 0
    assert cfg.n_expert_layers == 3 and cfg.kv_latent_dim == 28


# (a) the whole-sequence forward against the reference's logits
@pytest.mark.parametrize("T", [24, 5])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(T), (1, T), 3, 64)
    pos, seg = jnp.arange(T)[None], jnp.ones((1, T), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = hybrid.forward(params, cfg, toks, pos, seg)[0]
        logp = hybrid.logprobs_of_labels(params, cfg, toks, pos, seg)[0]
    want = ref.forward_logits(HF, params, np.asarray(toks[0]))
    assert got.shape == (T, 64) and float(jnp.std(want)) > 0.1
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    fn = ref.make_token_logps(HF)
    own = ref.sequence_logps(fn, params, [int(t) for t in toks[0]], pad_to=T)[0]
    assert np.abs(np.asarray(logp) - own).max() < 2e-5


# (c) the absorbed form against the unabsorbed one
def test_absorbed_attention_is_unabsorbed_attention(model):
    cfg, params = model
    ap = jax.tree.map(lambda a: a[2], params["latent"])
    B, Tq, Tk, H = 2, 3, 11, cfg.n_q_heads
    kq, kk = jax.random.split(jax.random.PRNGKey(7))
    hq = jax.random.normal(kq, (B, Tq, 32))
    hk = jax.random.normal(kk, (B, Tk, 32))
    scale = hybrid._attn_scale(cfg)
    with jax.default_matmul_precision("highest"):
        cs_q = hybrid.latent_rope_tables(cfg, jnp.arange(Tk, Tk + Tq)[None].repeat(B, 0))
        cs_k = hybrid.latent_rope_tables(cfg, jnp.arange(Tk)[None].repeat(B, 0))
        q_nope, q_rope = hybrid.latent_q(cfg, ap, hq, cs_q)
        c_kv, k_rope = hybrid.latent_kv(cfg, ap, hk, cs_k)
        # unabsorbed: keys and values expanded, per head
        k, v = hybrid.latent_expand(cfg, ap, c_kv, k_rope)
        s = jnp.einsum("bthd,buhd->bhtu", jnp.concatenate([q_nope, q_rope], -1), k)
        want = jnp.einsum("bhtu,buhv->bthv", jax.nn.softmax(s * scale, -1), v)
        # absorbed: queries against the cached entries themselves
        entry = hybrid.latent_entry(cfg, c_kv, k_rope)
        q_abs = hybrid.latent_absorbed_q(cfg, ap, q_nope, q_rope)
        assert entry.shape[-1] == q_abs.shape[-1] == paged.latent_page_width(cfg) == 128
        assert float(jnp.abs(entry[..., cfg.kv_latent_dim :]).max()) == 0.0
        s2 = jnp.einsum("bthc,buc->bhtu", q_abs, entry)
        o_lat = jnp.einsum(
            "bhtu,buc->bthc", jax.nn.softmax(s2 * scale, -1),
            entry[..., : cfg.kv_lora_rank],
        )
        got = hybrid.latent_values_out(cfg, ap, o_lat)
    assert want.shape == (B, Tq, H, cfg.v_head_dim)
    assert np.abs(np.asarray(s2 - s)).max() < 1e-5
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def _published_route(scores, bias, n_group, topk_group, k, scale):
    """The published rule, token by token, in numpy loops."""
    ids, weights = [], []
    for s in np.asarray(scores, np.float64):
        c = s + np.asarray(bias, np.float64)
        per = len(c) // n_group
        group = [np.sort(c[g * per : (g + 1) * per])[-2:].sum() for g in range(n_group)]
        keep = np.argsort(group)[::-1][:topk_group]
        masked = np.zeros_like(c)
        for g in keep:
            masked[g * per : (g + 1) * per] = c[g * per : (g + 1) * per]
        idx = np.argsort(-masked, kind="stable")[:k]
        w = s[idx] / s[idx].sum() * scale
        ids.append(idx)
        weights.append(w)
    return np.array(ids), np.array(weights)


# (d) the router against a direct transcription of the published rule
def test_router_is_the_published_rule():
    cfg = make_cfg()
    D, E = 32, 16
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (40, D))
    router = {
        "w": jax.random.normal(jax.random.fold_in(key, 1), (D, E)) * 0.3,
        "bias": jax.random.uniform(jax.random.fold_in(key, 2), (E,), minval=-0.2, maxval=0.2),
    }
    w, idx, logits, groups = moe.route(cfg, x, router)
    scores = jax.nn.sigmoid(logits)
    want_idx, want_w = _published_route(scores, router["bias"], 4, 2, 3, 2.5)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    order = np.argsort(np.asarray(idx), -1)
    got_w = np.take_along_axis(np.asarray(w), order, -1)
    want_sorted = np.take_along_axis(want_w, np.argsort(want_idx, -1), -1)
    assert np.abs(got_w - want_sorted).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    assert np.asarray(groups).sum(-1).tolist() == [2] * 40
    # the reference's own transcription says the same
    ref_idx, _ = ref.route(HF, scores, router["bias"])
    assert np.array_equal(np.sort(np.asarray(ref_idx[:, :3]), -1), np.sort(want_idx, -1))


def test_router_group_limit_and_choice_bias():
    """A token whose best expert lies in a group that is NOT chosen, and a
    bias that changes the choice but not the weights."""
    cfg = make_cfg()
    # groups of 4: the best single expert (0.99) sits alone in group 0;
    # groups 1 and 2 have two good ones each, so their top-2 sums win
    s = np.full((1, 16), 0.05, np.float32)
    s[0, 0] = 0.99
    s[0, [4, 5]] = [0.8, 0.7]
    s[0, [8, 9]] = [0.75, 0.72]
    logits = np.log(s / (1 - s))
    router = {"w": jnp.eye(16), "bias": jnp.zeros((16,))}
    w, idx, _, groups = moe.route(cfg, jnp.asarray(logits), router)
    assert np.asarray(groups)[0].tolist() == [False, True, True, False]
    assert sorted(np.asarray(idx)[0].tolist()) == [4, 8, 9]  # expert 0 is out
    assert np.allclose(np.sort(np.asarray(w)[0]),
                       np.sort(s[0, [4, 8, 9]] / s[0, [4, 8, 9]].sum() * 2.5), atol=1e-5)
    # a bias that lifts expert 5 over expert 9 changes WHICH are taken;
    # the weights are still the unbiased scores of those taken
    bias = np.zeros((16,), np.float32)
    bias[5] = 0.1
    w2, idx2, _, _ = moe.route(cfg, jnp.asarray(logits), dict(router, bias=jnp.asarray(bias)))
    assert sorted(np.asarray(idx2)[0].tolist()) == [4, 5, 8]
    assert np.allclose(np.sort(np.asarray(w2)[0]),
                       np.sort(s[0, [4, 5, 8]] / s[0, [4, 5, 8]].sum() * 2.5), atol=1e-5)
    # and a bias on an expert that is taken anyway changes nothing
    bias[:] = 0
    bias[4] = 0.1
    w3, idx3, _, _ = moe.route(cfg, jnp.asarray(logits), dict(router, bias=jnp.asarray(bias)))
    assert sorted(np.asarray(idx3)[0].tolist()) == [4, 8, 9]
    assert np.allclose(np.sort(np.asarray(w3)[0]), np.sort(np.asarray(w)[0]), atol=1e-6)


# (e) the shares add up: 8 chips x 2 of 16 experts (half a group each)
@pytest.mark.parametrize("n_tokens", [6, 40])
def test_shares_of_a_stated_split_add_up_to_the_uncut_layer(model, n_tokens):
    whole, params = model
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, n_tokens, 32))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._experts(HF, m[0], lp, first=0)
        no_shared = {k: v for k, v in lp.items() if k != "shared"}
        total, held_pairs, hits = 0.0, 0, 0
        for first in range(0, 16, 2):
            cfg = make_cfg(moe_first_expert=first, moe_held_experts=2)
            share = dict(
                no_shared,
                experts=jax.tree.map(lambda a: a[first : first + 2], lp["experts"]),
            )
            out, p, idx, _ = moe.held_moe_mlp(cfg, m, share)
            assert p.shape == (moe.n_pair_counts(cfg),) == (4,)
            total = total + out[0]
            held_pairs += int(p[:2].sum())
            assert int(p[:3].sum()) == n_tokens * 3
            hits += int(p[3])
            assert int(p[3]) <= n_tokens  # a chip lies in ONE group
        cfg = make_cfg(moe_first_expert=0, moe_held_experts=0)
        shared_only, _, _, _ = moe.held_moe_mlp(
            cfg, m, dict(lp, experts=jax.tree.map(lambda a: a[:0], lp["experts"]))
        )
    assert np.abs(np.asarray(total + shared_only[0] - want)).max() < 2e-5
    # every pair is held by exactly one chip; every chosen group is hit
    # on each of the 2 chips that share it
    assert held_pairs == n_tokens * 3 and hits == n_tokens * 2 * 2


# (g) the adapter: configuration and parameter names, both ways
def test_config_round_trips_on_the_catalog_rows_keys():
    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "benchmark", "configs",
        "gigachat3.1-702b-a36b.json",
    )
    with open(path) as f:
        published = json.load(f)["hf_config"]
    cfg = FAMILY.config_from_hf(published)
    back = FAMILY.config_to_hf(cfg)
    assert {k: back[k] for k in published} == published
    assert FAMILY.config_from_hf(back) == cfg
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_mtp_modules) == (64, 3, 1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim) == (1536, 512, 128, 64, 192, 192)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.moe_n_groups,
            cfg.moe_topk_groups, cfg.moe_routed_scale) == (256, 8, 8, 4, 2.5)
    assert cfg.shared_expert_dim == 2048 and cfg.intermediate_dim == 18432
    assert paged.latent_page_width(cfg) == 640


@pytest.mark.parametrize(
    "key,bad", [("scoring_func", "softmax"), ("topk_method", "greedy"),
                ("attention_bias", True), ("q_lora_rank", None)],
)
def test_config_refuses_what_the_stack_does_not_write(key, bad):
    with pytest.raises(NotImplementedError):
        FAMILY.config_from_hf(dict(HF, **{key: bad}))


def test_parameter_maps_round_trip_and_skip_the_mtp_module_by_name(model):
    cfg, params = model
    state = FAMILY.params_to_hf(params, cfg)
    # HF's own shapes and names
    assert state["model.layers.0.self_attn.kv_b_proj.weight"].shape == (4 * (8 + 6), 24)
    assert state["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].shape == (28, 32)
    assert state["model.layers.0.self_attn.q_b_proj.weight"].shape == (4 * 12, 16)
    assert state["model.layers.0.mlp.gate_proj.weight"].shape == (48, 32)
    assert state["model.layers.1.mlp.gate.weight"].shape == (16, 32)
    assert state["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (16,)
    assert state["model.layers.3.mlp.experts.15.down_proj.weight"].shape == (32, 16)
    assert state["model.layers.2.mlp.shared_experts.up_proj.weight"].shape == (16, 32)
    assert "model.layers.0.mlp.gate.weight" not in state  # a dense layer
    assert not any(n.startswith("model.layers.4.") for n in state)
    # a checkpoint carries its multi-token-prediction module as layer 4:
    # skipped by name, with a log line
    mtp = {
        "model.layers.4.enorm.weight": np.ones(32, np.float32),
        "model.layers.4.eh_proj.weight": np.ones((32, 64), np.float32),
        "model.layers.4.self_attn.o_proj.weight": np.ones((32, 24), np.float32),
    }
    assert deepseek_v3.mtp_weight_names(dict(state, **mtp), cfg) == sorted(mtp)
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    deepseek_v3.logger.addHandler(handler)
    try:
        back = FAMILY.params_from_hf(dict(state, **mtp), cfg)
    finally:
        deepseek_v3.logger.removeHandler(handler)
    (said,) = lines
    assert "multi-token-prediction" in said and "model.layers.4" in said
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_published_rope_pairs_are_the_programs_halves(model):
    """The checkpoint pairs rope dims (2j, 2j+1) and de-interleaves q and
    k at run time (``apply_rotary_pos_emb``); the adapter de-interleaves
    the weights once.  Rotating the published layout by pairs and the
    loaded one by halves gives the same scores."""
    cfg, params = model
    state = FAMILY.params_to_hf(params, cfg)
    w_pub = state["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"].T[:, 24:]  # [D, 4]
    w_own = np.asarray(params["latent"]["kv_a"]["w"][1])[:, 24:]
    x = np.random.default_rng(0).normal(size=(5, 32)).astype(np.float32)
    pos = np.arange(5, dtype=np.float32)[:, None]
    ang = pos * hybrid.rope_inv_freq(cfg, 4)[None]  # [5, 2]
    a = x @ w_pub  # pairs (0,1), (2,3)
    by_pairs = np.stack(
        [a[:, 0::2] * np.cos(ang) - a[:, 1::2] * np.sin(ang),
         a[:, 1::2] * np.cos(ang) + a[:, 0::2] * np.sin(ang)], -1
    ).reshape(5, 4)  # back in the published (interleaved) order
    cs = hybrid.latent_rope_tables(cfg, jnp.arange(5)[None])
    by_halves = np.asarray(
        hybrid.rope_apply(jnp.asarray(x @ w_own)[None, :, None, :], *cs)
    )[0, :, 0]
    # the same numbers, the halves being the published evens then odds
    assert np.abs(by_halves - by_pairs[:, [0, 2, 1, 3]]).max() < 1e-5


def test_a_share_imports_its_own_experts_and_cannot_be_exported(model):
    cfg, params = model
    state = FAMILY.params_to_hf(params, cfg)
    share = dataclasses.replace(cfg, moe_first_expert=4, moe_held_experts=2)
    held = FAMILY.params_from_hf(state, share)
    ex = held["layers"]["mlp"]["experts"]
    assert ex["gate"].shape == (3, 2, 16, 32)
    assert np.array_equal(
        np.asarray(ex["down"]),
        np.asarray(params["layers"]["mlp"]["experts"]["down"][:, 4:6]),
    )
    with pytest.raises(ValueError, match="share"):
        FAMILY.params_to_hf(held, share)


# (h) YaRN's frequencies and the softmax scale against hand numbers
def test_yarn_frequencies_and_softmax_scale_at_the_published_sizes():
    cfg = FAMILY.config_from_hf(dict(
        HF, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=192,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1, original_max_position_embeddings=4096,
                          rope_type="yarn"),
    ))
    f = hybrid.rope_inv_freq(cfg, 64)
    assert f.shape == (32,)
    # correction dims: 64 ln(4096 / (32 x 2 pi)) / (2 ln 1e5) = 8.378 ->
    # 8, and 64 ln(4096 / (2 pi)) / (2 ln 1e5) = 18.011 -> 19; dims below
    # 8 keep theta^(-2j/64), dims from 19 on are divided by 64, a linear
    # ramp between
    hand = {
        0: 1.0,
        8: 10 ** -1.25,  # 1e5^(-16/64), untouched
        13: 10 ** -2.03125 * (1 - 5 / 11 + 5 / 11 / 64),
        19: 10 ** -2.96875 / 64,
        31: 10 ** -4.84375 / 64,
    }
    for j, want in hand.items():
        assert abs(f[j] / want - 1) < 1e-5, (j, f[j], want)
    assert abs(hand[8] - 0.0562341) < 1e-6 and abs(hand[19] - 1.67908e-5) < 1e-9
    assert np.allclose(f, ref.rope_inv_freq(dict(HF, qk_rope_head_dim=64, rope_scaling=dict(
        beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
        original_max_position_embeddings=4096))), rtol=1e-6)
    # scale = 192^-0.5 x (0.1 ln 64 + 1)^2 = 0.0721688 x 1.415888^2
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.415888) < 1e-6
    assert abs(hybrid._attn_scale(cfg) - 0.144680) < 1e-6
    assert abs(hybrid._attn_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    # cos and sin carry mscale / mscale_all_dim = 1
    cos, sin = hybrid.latent_rope_tables(cfg, jnp.zeros((1, 1), jnp.int32))
    assert float(cos.max()) == 1.0 and float(jnp.abs(sin).max()) == 0.0


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


# fill pieces of 5 do not line up with the page of 8; the kernel forms run
# in interpret mode
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("piece", [5, 8, 13])
def test_fill_in_chunks_then_decode_through_latent_pages_is_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    S, BS, MB, slot = 4, 8, 8, 2
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    # ONE pool: a page's row is a token's [c_kv | k_rope | 0]; no V bytes
    assert k_pool.shape == (4, 16, 1, BS, 128) and v_pool.size == 0
    assert paged.kv_pool_layout_bytes(cfg, 16, BS) == (k_pool.nbytes, 0)
    ssm, conv = hybrid.state_zeros(cfg, S)
    assert ssm.size == conv.size == 0 and hybrid.state_layout_bytes(cfg, S) == 0
    k_pool = k_pool + 3.0  # a page is dirty when a fill takes it
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (13,), 3, 64))
    tables = np.zeros((2, MB), np.int32)
    tables[0, :4] = [3, 5, 7, 9]
    with jax.default_matmul_precision("highest"):
        pos, routed = 0, []
        while pos < len(prompt):
            take = min(piece, len(prompt) - pos)
            toks = np.zeros((2, 16), np.int32)
            toks[0, :take] = prompt[pos : pos + take]
            (logits, k_pool, v_pool, ssm, conv, pairs,
             r, _) = hybrid.hybrid_fill_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
                jnp.asarray(tables), jnp.asarray([slot, 0], jnp.int32),
                use_kernel=use_kernel,
            )
            # every valid token routed top-3 in each of the 3 expert layers
            assert int(pairs[:-1].sum()) == take * 3 * 3
            assert r.shape[0] == 3
            routed.append(np.asarray(r)[:, 0, :take].swapaxes(0, 1))
            pos += take
        want_logits = ref.forward_logits(HF, params, prompt)
        assert np.abs(np.asarray(logits[0]) - np.asarray(want_logits[-1])).max() < 2e-5
        lp0 = jax.nn.log_softmax(logits[0])
        first = int(jnp.argmax(lp0))
        full = np.zeros((S, MB), np.int32)
        full[slot, :4] = [3, 5, 7, 9]
        onehot = np.arange(S) == slot
        lens = jnp.asarray(np.where(onehot, 13, 0), jnp.int32)
        cur = jnp.asarray(np.where(onehot, first, 0), jnp.int32)
        act = jnp.asarray(onehot)
        bud = jnp.asarray(np.where(onehot, 9, 0), jnp.int32)
        seq, lps = list(prompt) + [first], [float(lp0[first])]
        for _ in range(3):
            (k_pool, v_pool, ssm, conv, lens, out_t, out_l, em, cur, act, bud,
             _, pairs, r) = hybrid.hybrid_decode_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(full), lens,
                cur, act, bud, jax.random.PRNGKey(0), 4, _greedy, _never_stop,
                use_kernel=use_kernel, max_len=64,
            )
            e = np.asarray(em[slot])
            seq += list(np.asarray(out_t[slot])[e])
            lps += list(np.asarray(out_l[slot])[e])
            routed.append(np.asarray(r)[e, :, :, slot])
    assert len(seq) == 13 + 10
    # pages of other rows were never touched, nor this row's unused ones
    assert float(jnp.abs(k_pool[:, [0, 1, 2, 4, 6, 8]] - 3.0).max()) == 0.0
    # what the pool holds of the row: its 22 cached entries, padding zero
    assert float(jnp.abs(k_pool[:, [3, 5], :, :, cfg.kv_latent_dim :]).max()) == 0.0
    fn = ref.make_token_logps(HF)
    ints = [int(t) for t in seq]
    want = ref.sequence_logps(fn, params, ints, pad_to=32)[0][12:]
    assert np.abs(np.asarray(lps) - want).max() < 2e-5
    # the routing the two programs hand out, and a reference that follows it
    routed = np.concatenate(routed)
    assert routed.shape == (len(seq) - 1, 3, 3)
    followed, _, flips = ref.sequence_logps(fn, params, ints, routed=routed, pad_to=32)
    assert int(flips.sum()) == 0 and np.abs(followed[12:] - want).max() < 1e-6
    other = (routed + 1) % 16
    moved, _, flips = ref.sequence_logps(fn, params, ints, routed=other, pad_to=32)
    assert int(flips.min()) == 3 and np.abs(moved[12:] - want).max() > 1e-4


def test_the_float8_control_is_another_model(model):
    cfg, params = model
    seq = [int(t) for t in np.random.default_rng(0).integers(3, 64, 20)]
    exact = ref.sequence_logps(ref.make_token_logps(HF), params, seq, pad_to=32)[0]
    low = ref.make_token_logps(HF, low=("weights", "float8_e4m3fn"))
    rounded = ref.sequence_logps(low, params, seq, pad_to=32)[0]
    assert 1e-3 < np.abs(rounded - exact).max() < 1.0
