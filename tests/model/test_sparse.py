"""dots3_note's two latent mixers in a stack stated by kind
(models/hybrid.py: ``latent`` under a learned indexer's choice,
``latent_window`` at widths of its own), the headwise gate, the latent
rescale and the group-less ``noaux_tc`` router, against the benchmark's
plain reference, on the CPU at a tiny size: 1 dense + 4 expert layers
(full, full, window, window, window: one period), 16 experts top 3, one
shared expert, an indexer that keeps 6 positions, a window of 5; seeded
weights, float32.  The reference (benchmark/lib/reference_dots3_note.py)
calls no model code and makes its OWN selection (``top_k`` and a scatter
where the program counts bit by bit).

Tolerances: 2e-5 on logits and log-probabilities of deviation ~0.6 at
float32 (two implementations of the same sums in another order: the
latent cell's); a DROPPED piece (selection, gate, rescale, window) moves
them by 1e-3 or more and is asserted to."""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, moe, paged
from areal_tpu.models.hf import dots3_note
from areal_tpu.models.hf.registry import family_from_architecture
from areal_tpu.ops import sparse_attention as sparse
from benchmark.lib import reference_dots3_note as ref

ARCH = "Dots3NoteForCausalLM"
# no two widths alike that the published model has alike (value heads 6
# against nope 8 in full layers, 10 / 6 / 4 heads-of-2 in window layers),
# so nothing can lean on an equality; the index key (12) is wider than the
# rope part (4) it ropes
HF = dict(
    architectures=[ARCH], model_type="dots3_note", vocab_size=64,
    max_position_embeddings=256, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention"] + ["sliding_attention"] * 3,
    num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
    n_routed_experts=16, routed_scaling_factor=1,
    kv_lora_rank=24, q_lora_rank=16, qk_rope_head_dim=4, v_head_dim=6,
    qk_nope_head_dim=8, index_n_heads=3, index_head_dim=12, index_topk=6,
    attention_gate_type="headwise", apply_mla_qkv_lora_rescale=True,
    sliding_window_size=5, swa_attention_gate_type="headwise",
    swa_kv_lora_rank=20, swa_num_attention_heads=2, swa_num_key_value_heads=2,
    swa_q_lora_rank=12, swa_qk_nope_head_dim=10, swa_qk_rope_head_dim=4,
    swa_rope_theta=5000, swa_v_head_dim=7,
    topk_method="noaux_tc", num_experts_per_tok=3, moe_layer_freq=1,
    first_k_dense_replace=1, norm_topk_prob=True, scoring_func="sigmoid",
    hidden_act="silu", rms_norm_eps=1e-5, rope_theta=80000000,
    rope_scaling=None, attention_bias=False, tie_word_embeddings=False,
    torch_dtype="bfloat16",
)
FAMILY = family_from_architecture(ARCH)


def make_cfg(**over):
    return dataclasses.replace(FAMILY.config_from_hf(HF), dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _greedy(logits, rng, row_seeds=None, positions=None):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tok = jnp.argmax(lp, axis=-1).astype(jnp.int32)
    return tok, jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


def _never_stop(tok):
    return jnp.zeros(tok.shape, bool)


def test_the_config_states_both_kinds_widths_and_the_plan(model):
    cfg, params = model
    assert cfg.layer_types == ("latent", "latent") + ("latent_window",) * 3
    assert cfg.is_latent and cfg.is_latent_window and cfg.is_indexed
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_latent_window_layers) == (5, 3, 3)
    w = cfg.window_latent()
    assert (w.n_q_heads, w.q_lora_rank, w.kv_lora_rank, w.head_dim,
            w.v_head_dim, w.rotary_base) == (2, 12, 20, 14, 7, 5000.0)
    assert not w.is_indexed and w.attention_gate == "headwise"
    assert (cfg.kv_latent_dim, w.kv_latent_dim) == (28, 24)
    plan = hybrid.layer_plan(cfg)
    assert [tuple(r)[:8] for r in plan] == [
        ("latent", "dense", 0, 0, 0, 1, True, 0),
        ("latent", "experts", 1, 1, 0, 1, True, 1),
        ("latent_window", "experts", 2, 0, 1, 3, True, 0),
    ]
    # head counts and every attention width differ by kind: two stacks
    assert params["latent"]["o"]["w"].shape == (2, 4 * 6, 32)
    assert params["latent_window"]["o"]["w"].shape == (3, 2 * 7, 32)
    assert params["latent"]["gate"]["w"].shape == (2, 32, 4)
    assert params["latent_window"]["gate"]["w"].shape == (3, 32, 2)
    assert params["latent"]["index_q"]["w"].shape == (2, 16, 3 * 12)
    assert "index_q" not in params["latent_window"]
    # one page format a POOL: entries of each kind's width, index keys
    # beside the global layers' entries
    (k, v), (wk, wv) = paged.pool_shapes(cfg, 6, 8), paged.pool_shapes(cfg, 6, 8, 3)
    assert (k, v) == ((2, 6, 1, 8, 128), (2, 6, 1, 8, 12))
    assert (wk, wv) == ((3, 6, 1, 8, 128), (3, 6, 1, 8, 0))
    assert paged.kv_pool_layout_bytes(cfg, 6, 8) == (2 * 6 * 8 * (128 + 12) * 4, 0)


@pytest.mark.parametrize(
    "kinds", [("latent", "attention"), ("window", "latent_window", "latent"),
              ("latent", "window"), ("attention", "latent_window")],
)
def test_a_pool_holds_one_page_format(kinds):
    with pytest.raises(AssertionError):
        make_cfg(layer_types=kinds + ("latent",) * (5 - len(kinds)))


# (a) the whole-sequence forward against the reference's logits
@pytest.mark.parametrize("T", [24, 5])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(T), (1, T), 3, 64)
    pos, seg = jnp.arange(T)[None], jnp.ones((1, T), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = hybrid.forward(params, cfg, toks, pos, seg)[0]
    want = ref.forward_logits(HF, params, np.asarray(toks[0]))
    assert got.shape == (T, 64) and float(jnp.std(want)) > 0.1
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


# (b) each piece is THERE: a reference that drops it is another model
@pytest.mark.parametrize(
    "wrong", ["select_off", "gate_off", "rescale_off", "window_off"]
)
def test_a_dropped_piece_is_another_model(model, wrong):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 3, 64)
    pos, seg = jnp.arange(24)[None], jnp.ones((1, 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(hybrid.forward(params, cfg, toks, pos, seg)[0])
    bad = np.asarray(ref.forward_logits(HF, params, np.asarray(toks[0]), wrong=wrong))
    assert np.abs(got - bad).max() > 1e-3, wrong


def _peaked(params):
    """Weights under which a full layer's attention is PEAKED on a few
    positions the indexer does NOT choose: queries and keys scaled up (a
    softmax over scores of deviation ~20 puts its mass on one or two
    positions), the indexer's weights left as they are."""
    lat = dict(params["latent"])
    lat["q_b"] = {"w": lat["q_b"]["w"] * 6.0}
    lat["k_b"] = {"w": lat["k_b"]["w"] * 6.0}
    return dict(params, latent=lat)


def test_peaked_attention_fails_a_full_layer_that_attends_its_whole_context(model):
    cfg, params = model
    params = _peaked(params)
    T = 40
    toks = jax.random.randint(jax.random.PRNGKey(11), (1, T), 3, 64)
    pos, seg = jnp.arange(T)[None], jnp.ones((1, T), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(hybrid.forward(params, cfg, toks, pos, seg)[0])
    want = np.asarray(ref.forward_logits(HF, params, np.asarray(toks[0])))
    whole = np.asarray(
        ref.forward_logits(HF, params, np.asarray(toks[0]), wrong="select_off")
    )
    assert np.abs(got - want).max() < 5e-5
    # past the indexer's 6 positions, a layer that attended everything
    # would have found the peak the chosen set leaves out
    assert np.abs(whole[8:] - want[8:]).max() > 0.05
    assert np.abs(got[:6] - whole[:6]).max() < 5e-5  # (all chosen so far)


# (c) the selection is EXACT and the reference's own
def test_the_mask_form_is_top_k_with_its_ties():
    s = jnp.round(jax.random.normal(jax.random.PRNGKey(0), (7, 90)) * 3) / 3
    s = s.at[3].set(0.25).at[:, 70:].set(sparse.NEG)  # ties everywhere
    for k in (1, 6, 33, 70, 90, 200):
        idx, live = sparse.select(s, k)
        want = np.zeros(s.shape, bool)
        for b in range(s.shape[0]):
            want[b, np.asarray(idx[b])[np.asarray(live[b])]] = True
        got = np.asarray(sparse.chosen_mask(s, k))
        assert (got == want).all() and got.sum(1).max() <= min(k, 70)
        allowed = np.asarray(s) > sparse.NEG / 2
        assert (np.asarray(ref.chosen(s, allowed, k)).sum(1) == got.sum(1)).all()


# (d) fill in chunks, then decode, through both pools and the index pool
S, BS, SLOT = 4, 8, 2
PAGES, WIN_PAGES = [3, 5, 7, 9], [2, 4, 6, 8]


def _fill_prompt(cfg, params, prompt, piece, use_kernel):
    """The prompt prefilled ``piece`` tokens at a time into dirty pools
    (row 0 of a fill batch of two, for state slot ``SLOT``): ``(the last
    chunk's logits, k_pool, v_pool, ssm, conv, win pools)``."""
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    win = paged.pool_zeros(cfg, 12, BS, layers=3)
    ssm, conv = hybrid.state_zeros(cfg, S)
    k_pool, v_pool = k_pool + 3.0, v_pool - 2.0  # dirty pages
    win = (win[0] + 1.5, win[1])
    tables, wtables = np.zeros((2, 8), np.int32), np.zeros((2, 8), np.int32)
    tables[0, :4], wtables[0, :4] = PAGES, WIN_PAGES
    pos = 0
    while pos < len(prompt):
        take = min(piece, len(prompt) - pos)
        toks = np.zeros((2, 16), np.int32)
        toks[0, :take] = prompt[pos : pos + take]
        (logits, k_pool, v_pool, ssm, conv, pairs, r, _,
         win) = hybrid.hybrid_fill_chunk(
            params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
            jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
            jnp.asarray(tables), jnp.asarray([SLOT, 0], jnp.int32),
            use_kernel=use_kernel, win_pools=win,
            win_tables=jnp.asarray(wtables),
        )
        assert int(pairs[:-1].sum()) == take * 3 * 4 and len(win) == 2
        pos += take
    return logits, k_pool, v_pool, ssm, conv, win


def _decode_chunks(cfg, params, state, first, n_cached, use_kernel, MB, chunks=3):
    """``chunks`` decode chunks of 4 steps of the row in ``SLOT`` over
    tables of ``MB`` pages: ``(tokens, log-probabilities, kept sets [steps,
    full layers, 6] as POSITIONS of the row, the pools)``.  The kept sets
    come as the path hands them out: masks over the table and then the
    chunk's own tokens, 32 to a word, where the steps attend under a mask
    (``sparse.decode_reads_masked``: a table of up to 16 x 6 = 96
    positions), positions where they gather."""
    k_pool, v_pool, ssm, conv, win = state
    full, wfull = np.zeros((S, MB), np.int32), np.zeros((S, MB), np.int32)
    full[SLOT, :4], wfull[SLOT, :4] = PAGES, WIN_PAGES
    onehot = np.arange(S) == SLOT
    lens = jnp.asarray(np.where(onehot, n_cached, 0), jnp.int32)
    cur = jnp.asarray(np.where(onehot, first, 0), jnp.int32)
    act = jnp.asarray(onehot)
    bud = jnp.asarray(np.where(onehot, 9, 0), jnp.int32)
    masked = sparse.decode_reads_masked(MB * BS, cfg.index_topk)
    toks, lps, sets = [], [], []
    for _ in range(chunks):
        cached = int(lens[SLOT])
        (k_pool, v_pool, ssm, conv, lens, out_t, out_l, em, cur, act, bud,
         _, pairs, r, chosen, win) = hybrid.hybrid_decode_chunk(
            params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(full), lens,
            cur, act, bud, jax.random.PRNGKey(0), 4, _greedy, _never_stop,
            use_kernel=use_kernel, max_len=64, win_pools=win,
            win_tables=jnp.asarray(wfull), keep_chosen=True,
        )
        e = np.asarray(em[SLOT])
        toks += list(np.asarray(out_t[SLOT])[e])
        lps += list(np.asarray(out_l[SLOT])[e])
        kept = np.asarray(chosen)[e][:, :, SLOT]
        assert kept.dtype == (np.uint32 if masked else np.int32)
        if masked:
            assert kept.shape[-1] == -(-(MB * BS + 4) // 32)
        # either form, read by what it is (as the engine reads them)
        sets += [
            np.stack([
                sparse.kept_positions(row, MB * BS, cached, cfg.index_topk)
                for row in step
            ])
            for step in kept
        ]
    return toks, lps, np.stack(sets), (k_pool, v_pool, win)


#: tables of 8 pages of 8 (64 positions: under the mask) and of 13 (104:
#: past 16 x the 6 chosen, gathered)
@pytest.mark.parametrize(
    "piece,use_kernel,MB",
    [(5, False, 8), (5, True, 8), (13, False, 8), (13, True, 8),
     (13, False, 13), (13, True, 13)],
)
def test_fill_in_chunks_then_decode_through_pages_is_the_reference(
    model, use_kernel, piece, MB
):
    cfg, params = model
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (13,), 3, 64))
    with jax.default_matmul_precision("highest"):
        logits, *state = _fill_prompt(cfg, params, prompt, piece, use_kernel)
        want_logits = ref.forward_logits(HF, params, prompt)
        assert np.abs(np.asarray(logits[0]) - np.asarray(want_logits[-1])).max() < 2e-5
        lp0 = jax.nn.log_softmax(logits[0])
        first = int(jnp.argmax(lp0))
        toks, lps, sets, (k_pool, v_pool, win) = _decode_chunks(
            cfg, params, state, first, 13, use_kernel, MB
        )
    seq, lps = list(prompt) + [first] + toks, [float(lp0[first])] + lps
    assert len(seq) == 13 + 10
    # pages of other rows were never touched, in any of the three pools
    assert float(jnp.abs(k_pool[:, [0, 1, 2, 4, 6, 8]] - 3.0).max()) == 0.0
    assert float(jnp.abs(v_pool[:, [0, 1, 2, 4, 6, 8]] + 2.0).max()) == 0.0
    assert float(jnp.abs(win[0][:, [0, 1, 3, 5, 7]] - 1.5).max()) == 0.0
    # the index keys ride the latent entries' pages: the same 22 positions
    assert float(jnp.abs(v_pool[:, [3, 5]] + 2.0).min()) > 0.0
    fn = ref.make_token_logps(HF)
    ints = [int(t) for t in seq]
    at = np.arange(13, 22)  # the decode steps' query positions
    want, _, _, scores = ref.sequence_logps(fn, params, ints, pad_to=32, keep_at=at)
    assert np.abs(np.asarray(lps) - want[12:]).max() < 2e-5
    # the chosen sets are the reference's: 6 positions a step and layer,
    # none after the query's own, and nothing else was attended
    assert sets.shape == (9, 2, 6) and scores.shape == (2, 9, 23)
    for i, t in enumerate(at):
        for f in range(2):
            row = ref.selection_agreement(scores[f, i], sets[i, f], int(t), 6, 0.0)
            assert row["agree"] == 1.0 and row["within"], (t, f, row)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("scores", ["drawn", "all_tied"])
def test_a_decode_chunk_under_the_mask_is_the_gathering_chunk_step_for_step(
    model, use_kernel, scores
):
    """The two forms of ONE algorithm, picked by the table's shape: the
    same pools under tables of 8 pages (64 positions, under 16 x 6: the
    steps attend under the mask, in the paged kernel) and of 13 (104: they
    gather): the same tokens, the same log-probabilities, the same kept
    sets as positions, ``top_k``'s, where every index score is the same
    too (the heads' weights zeroed: the LOWEST positions are chosen)."""
    cfg, params = model
    if scores == "all_tied":
        lat = dict(params["latent"])
        lat["index_w"] = jax.tree.map(jnp.zeros_like, lat["index_w"])
        params = dict(params, latent=lat)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (13,), 3, 64))
    assert sparse.decode_reads_masked(8 * BS, cfg.index_topk)
    assert not sparse.decode_reads_masked(13 * BS, cfg.index_topk)
    with jax.default_matmul_precision("highest"):
        logits, *state = _fill_prompt(cfg, params, prompt, 13, use_kernel)
        first = int(jnp.argmax(logits[0]))
        # (a decode chunk is given its pools and state to keep: a copy each)
        masked, gather = (
            _decode_chunks(
                cfg, params, jax.tree.map(jnp.copy, state), first, 13,
                use_kernel, MB, 2,
            )
            for MB in (8, 13)
        )
    assert len(masked[0]) == 8 and masked[0] == gather[0]
    assert np.abs(np.asarray(masked[1]) - np.asarray(gather[1])).max() < 2e-5
    assert masked[2].shape == gather[2].shape == (8, 2, 6)
    assert (np.sort(masked[2], axis=-1) == np.sort(gather[2], axis=-1)).all()
    assert (masked[2] >= 0).all()
    if scores == "all_tied":
        assert (masked[2] == np.arange(6)).all()
    for a, b in zip(masked[3][:2], gather[3][:2]):
        assert float(jnp.abs(a - b).max()) < 2e-5


# (e) the sixteen shares add up to the uncut layer (router without groups)
def test_shares_of_a_stated_split_add_up_to_the_uncut_layer(model):
    whole, params = model
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 20, 32))
    hf1 = dict(HF, n_group=1, topk_group=1)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._experts(hf1, m[0], lp, first=0)
        no_shared = {k: v for k, v in lp.items() if k != "shared"}
        total, held_pairs = 0.0, 0
        for first in range(16):
            cfg = make_cfg(moe_first_expert=first, moe_held_experts=1)
            share = dict(
                no_shared,
                experts=jax.tree.map(lambda a: a[first : first + 1], lp["experts"]),
            )
            out, p, idx, _ = moe.held_moe_mlp(cfg, m, share)
            total = total + out[0]
            held_pairs += int(p[:1].sum())
        cfg = make_cfg(moe_first_expert=0, moe_held_experts=0)
        shared_only, _, _, _ = moe.held_moe_mlp(
            cfg, m, dict(lp, experts=jax.tree.map(lambda a: a[:0], lp["experts"]))
        )
    # the shared expert counted once
    assert np.abs(np.asarray(total + shared_only[0] - want)).max() < 2e-5
    assert held_pairs == 20 * 3


# (f) the adapter: configuration and parameter names, both ways
def test_config_round_trips_and_refuses_what_the_stack_does_not_write():
    cfg = FAMILY.config_from_hf(HF)
    back = FAMILY.config_to_hf(cfg)
    assert {k: back[k] for k in HF} == HF
    assert FAMILY.config_from_hf(back) == cfg
    assert (cfg.moe_router, cfg.moe_n_groups, cfg.moe_topk_groups) == (
        "sigmoid_group", 1, 1
    )
    assert cfg.mla_lora_rescale and cfg.attention_gate == "headwise"
    for key, bad in (
        ("scoring_func", "softmax"), ("attention_bias", True), ("n_group", 8),
        ("rope_scaling", {"rope_type": "yarn"}), ("attention_gate_type", "elementwise"),
    ):
        with pytest.raises(NotImplementedError):
            FAMILY.config_from_hf(dict(HF, **{key: bad}))


def test_parameter_maps_round_trip_and_skip_towers_and_drafting_head_by_name(model):
    cfg, params = model
    state = FAMILY.params_to_hf(params, cfg)
    att = "model.layers.{}.self_attn."
    assert state[att.format(1) + "kv_b_proj.weight"].shape == (4 * (8 + 6), 24)
    assert state[att.format(2) + "kv_b_proj.weight"].shape == (2 * (10 + 7), 20)
    assert state[att.format(0) + "gate_proj.weight"].shape == (4, 32)
    assert state[att.format(4) + "gate_proj.weight"].shape == (2, 32)
    assert state[att.format(1) + "indexer.wq_b.weight"].shape == (3 * 12, 16)
    assert state[att.format(1) + "indexer.wk.weight"].shape == (12, 32)
    assert state[att.format(1) + "indexer.weights_proj.weight"].shape == (3, 32)
    assert att.format(2) + "indexer.wk.weight" not in state  # a window layer
    extra = {
        "vision_tower.blocks.0.attn.qkv.weight": np.ones((4, 4), np.float32),
        "audio_tower.conv1.weight": np.ones((4, 4), np.float32),
        "multi_modal_projector.linear.weight": np.ones((4, 4), np.float32),
        "model.layers.5.eh_proj.weight": np.ones((32, 64), np.float32),
    }
    assert dots3_note.not_served_names(dict(state, **extra), cfg) == sorted(
        n for n in extra if not n.startswith("model.")
    ) + ["model.layers.5.eh_proj.weight"]
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    dots3_note.logger.addHandler(handler)
    try:
        back = FAMILY.params_from_hf(dict(state, **extra), cfg)
    finally:
        dots3_note.logger.removeHandler(handler)
    (said,) = lines
    assert "towers" in said and "drafting head" in said and "4 weights" in said
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_share_cannot_be_exported(model):
    cfg, params = model
    with pytest.raises(ValueError):
        FAMILY.params_to_hf(params, make_cfg(moe_held_experts=4))
