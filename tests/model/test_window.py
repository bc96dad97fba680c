"""Window and global attention layers in one stack (models/hybrid.py, the
smallthinker family) against the benchmark's plain reference, on the CPU at
a tiny size: two periods ``[global, window, window, window]``, a window of
12 positions, 8 ReLU-gated experts top 3, a router that reads the
attention's input; seeded weights, float32.  The reference
(benchmark/lib/reference_smallthinker.py) calls no model code: it is a
second implementation of the published equations.

Every tolerance here is 2e-5 on log-probabilities or logits of deviation
~0.6, float32 against float32 at "highest" precision: what separates the
two is the order of float32 sums (readings: 5e-7 to 1e-6).  bfloat16 where
float32 is stated reads 1e-2, a window off by one page, RoPE on a global
layer or the router fed the wrong input 0.2-1.1 (the last three are tests
below)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, moe, paged
from areal_tpu.models.hf.registry import family_from_architecture, get_hf_family
from benchmark.lib import reference_smallthinker as ref

WINDOW = 12
HF = dict(
    architectures=["SmallThinkerForCausalLM"], hidden_size=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, moe_ffn_hidden_size=16, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling=None, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=WINDOW,
    tie_word_embeddings=False, vocab_size=64, max_position_embeddings=256,
)
TOL = 2e-5


def make_cfg(**over):
    cfg = family_from_architecture(HF["architectures"][0]).config_from_hf(HF)
    return dataclasses.replace(cfg, dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _forward(params, cfg, toks):
    T = len(toks)
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            hybrid.forward(
                params, cfg, jnp.asarray(toks)[None], jnp.arange(T)[None],
                jnp.ones((1, T), jnp.int32),
            )[0]
        )


def test_the_config_states_kinds_rope_router_and_activation():
    cfg = make_cfg()
    assert cfg.layer_types == ("attention", "window", "window", "window") * 2
    assert cfg.rope_layers == (False, True, True, True) * 2
    assert (cfg.activation, cfg.moe_router, cfg.moe_router_input) == (
        "relu", "topk_softmax", "attn",
    )
    assert (cfg.sliding_window, cfg.n_window_layers, cfg.n_attn_layers) == (
        WINDOW, 6, 8,
    )
    back = get_hf_family("smallthinker").config_to_hf(cfg)
    for key in (
        "sliding_window_layout", "rope_layout", "sliding_window_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "moe_ffn_hidden_size", "head_dim", "num_key_value_heads",
    ):
        assert back[key] == HF[key], key


def test_layer_plan_cuts_the_layouts_into_runs_with_a_pool_number_each():
    plan = hybrid.layer_plan(make_cfg())
    assert [
        (r.kind, r.first_layer, r.first_of_kind, r.first_in_pool, r.count, r.rope)
        for r in plan
    ] == [
        ("attention", 0, 0, 0, 1, False), ("window", 1, 1, 0, 3, True),
        ("attention", 4, 4, 1, 1, False), ("window", 5, 5, 3, 3, True),
    ]
    cfg = make_cfg()
    assert list(hybrid.pool_layer_numbers(cfg, "attention")) == [0, 4]
    assert list(hybrid.pool_layer_numbers(cfg, "window")) == [1, 2, 3, 5, 6, 7]
    # the pools: two global layers, six window layers
    assert paged.pool_shapes(cfg, 4, 8)[0][0] == 2
    assert paged.pool_shapes(cfg, 4, 8, layers=cfg.n_window_layers)[0][0] == 6


# 40 positions cross the window of 12 three times; 9 lie inside it
@pytest.mark.parametrize("T", [40, 9])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(T), (T,), 3, 64))
    want = np.asarray(ref.forward_logits(HF, params, toks))
    assert np.abs(_forward(params, cfg, toks) - want).max() < TOL


@pytest.mark.parametrize(
    "wrong, program",
    [
        ("window_off", dict(layer_types=("attention",) * 8)),
        ("rope_on_global", dict(rope_layers=(True,) * 8)),
        ("router_reads_m", dict(moe_router_input="mlp")),
    ],
)
def test_each_mistake_fails_the_tolerance_in_reference_and_program_alike(
    model, wrong, program
):
    """The window left off, RoPE on the global layers, the router fed the
    experts' input: each moves the logits by far more than the tolerance,
    whether the reference makes the mistake or the program does, and the
    two agree again when BOTH make it (so each flag is the same mistake)."""
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (40,), 3, 64))
    right = np.asarray(ref.forward_logits(HF, params, toks))
    mistaken = np.asarray(ref.forward_logits(HF, params, toks, wrong=wrong))
    assert np.abs(mistaken - right).max() > 1000 * TOL
    got = _forward(params, dataclasses.replace(cfg, **program), toks)
    assert np.abs(got - right).max() > 1000 * TOL
    assert np.abs(got - mistaken).max() < TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(model):
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (40,), 3, 64))
    right = np.asarray(ref.forward_logits(HF, params, toks))
    low = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    got = _forward(low, dataclasses.replace(cfg, dtype="bfloat16"), toks)
    assert np.abs(got.astype(np.float32) - right).max() > 100 * TOL


def test_the_router_reads_the_mixers_input_and_the_experts_their_own():
    """``held_moe_mlp`` routes on ``router_input`` and multiplies ``h``: with
    the two swapped both the routing and the output change."""
    cfg = make_cfg()
    p = jax.tree.map(
        lambda t: t[0], hybrid.init_params(cfg, jax.random.PRNGKey(1))["layers"]["mlp"]
    )
    a, m = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 24, 32))
    out, _, idx, _ = moe.held_moe_mlp(cfg, m, p, router_input=a)
    w, want_idx, _, _ = moe.route(cfg, a[0], p["router"])
    assert np.array_equal(np.asarray(idx[0]), np.asarray(want_idx))
    gate, up, down = (np.asarray(p["experts"][k]) for k in ("gate", "up", "down"))
    x = np.asarray(m[0])
    want = np.zeros_like(x)
    for t in range(24):
        for k in range(3):
            e = int(want_idx[t, k])
            hid = np.maximum(gate[e] @ x[t], 0.0) * (up[e] @ x[t])  # ReLU gate
            want[t] += float(w[t, k]) * (hid @ down[e])
    assert np.abs(np.asarray(out[0]) - want).max() < 1e-5
    swapped, _, idx2, _ = moe.held_moe_mlp(cfg, a, p, router_input=m)
    assert not np.array_equal(np.asarray(idx2), np.asarray(idx))
    assert np.abs(np.asarray(swapped) - np.asarray(out)).max() > 1e-2


@pytest.mark.parametrize("kind", ["relu", "silu", "gelu"])
def test_dense_expert_compute_gates_by_the_stated_activation(kind):
    N, D, E, F = 6, 7, 3, 5
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (N, D))
    gate, up, down = (jax.random.normal(k, (E, F, D)) for k in ks[1:4])
    w_tok = jax.random.uniform(ks[4], (N, E))
    got = moe.dense_expert_compute(x, w_tok, gate, up, down, kind)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}[kind]
    want = sum(
        w_tok[:, e, None] * ((act(x @ gate[e].T) * (x @ up[e].T)) @ down[e])
        for e in range(E)
    )
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    # a gate below zero passes nothing under ReLU, and something otherwise
    x1 = jnp.ones((1, D))
    shut = moe.dense_expert_compute(
        x1, jnp.ones((1, E)), -jnp.abs(gate), up, down, kind
    )
    assert (float(jnp.abs(shut).max()) == 0.0) == (kind == "relu")


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


# a prompt of 29 crosses the window of 12 twice and the page of 8 three
# times; fill pieces of 5 and 13 line up with neither; 20 more tokens are
# decoded in chunks of 4 while the pages behind the window are taken out
# of the window layers' table; the kernel forms run in interpret mode
@pytest.mark.parametrize("use_kernel, piece", [(False, 16), (False, 5), (True, 13)])
def test_fill_in_chunks_then_decode_through_two_pools_is_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    S, BS, MB, slot, P, W = 4, 8, 10, 2, 29, 4
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    win = paged.pool_zeros(cfg, 16, BS, layers=cfg.n_window_layers)
    ssm, conv = hybrid.state_zeros(cfg, S)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (P,), 3, 64))
    tables = np.zeros((2, MB), np.int32)
    tables[0, :8] = [3, 5, 7, 9, 11, 13, 1, 2]
    wtables = np.zeros((2, MB), np.int32)
    wtables[0, :8] = [4, 6, 8, 10, 12, 14, 15, 1]
    with jax.default_matmul_precision("highest"):
        pos = 0
        while pos < P:
            take = min(piece, P - pos)
            toks = np.zeros((2, 16), np.int32)
            toks[0, :take] = prompt[pos : pos + take]
            # what lies wholly before the window of this chunk's first
            # token is no longer in the table (its entries read page 0,
            # which holds another row's values)
            wt = wtables.copy()
            wt[0, : max(pos - WINDOW + 1, 0) // BS] = 0
            (logits, k_pool, v_pool, ssm, conv, pairs, r, _,
             win) = hybrid.hybrid_fill_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
                jnp.asarray(tables), jnp.asarray([slot, 0], jnp.int32),
                use_kernel=use_kernel, win_pools=win, win_tables=jnp.asarray(wt),
            )
            assert int(pairs.sum()) == take * 3 * 8
            pos += take
        lp0 = jax.nn.log_softmax(logits[0])
        first = int(jnp.argmax(lp0))
        full, wfull = np.zeros((2, S, MB), np.int32)
        full[slot], wfull[slot] = tables[0], wtables[0]
        onehot = np.arange(S) == slot
        lens = jnp.asarray(np.where(onehot, P, 0), jnp.int32)
        cur = jnp.asarray(np.where(onehot, first, 0), jnp.int32)
        act = jnp.asarray(onehot)
        bud = jnp.asarray(np.where(onehot, 21, 0), jnp.int32)
        seq, lps = list(prompt) + [first], [float(lp0[first])]
        for _ in range(5):
            wfull[slot, : max(int(lens[slot]) - WINDOW + 1, 0) // BS] = 0
            (k_pool, v_pool, ssm, conv, lens, out_t, out_l, em, cur, act, bud,
             _, pairs, r, win) = hybrid.hybrid_decode_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(full), lens,
                cur, act, bud, jax.random.PRNGKey(0), W, _greedy, _never_stop,
                use_kernel=use_kernel, max_len=128, win_pools=win,
                win_tables=jnp.asarray(wfull),
            )
            e = np.asarray(em[slot])
            seq += list(np.asarray(out_t[slot])[e])
            lps += list(np.asarray(out_l[slot])[e])
    assert len(seq) == P + 21
    fn = ref.make_token_logps(HF)
    want = ref.sequence_logps(fn, params, [int(t) for t in seq], pad_to=32)[0]
    assert np.abs(np.asarray(lps) - want[P - 1 :]).max() < TOL
    # a window off by one page is not the reference: the same sequence
    # under a window a page longer moves the log-probabilities
    longer = ref.make_token_logps(dict(HF, sliding_window_size=WINDOW + BS))
    moved = ref.sequence_logps(longer, params, [int(t) for t in seq], pad_to=32)[0]
    assert np.abs(moved[P - 1 :] - want[P - 1 :]).max() > 100 * TOL


def test_adapter_names_and_shapes_go_there_and_back(model):
    """The family's weight names (by its published modules: random weights
    have none, so names and shapes are held together here): every tensor of
    a tree exported under them comes back where it was."""
    cfg, params = model
    fam = get_hf_family("smallthinker")
    state = fam.params_to_hf(params, cfg)
    E, F, D = 8, 16, 32
    assert state["model.layers.3.block_sparse_moe.primary_router.weight"].shape == (E, D)
    assert state["model.layers.7.block_sparse_moe.experts.5.gate.weight"].shape == (F, D)
    assert state["model.layers.7.block_sparse_moe.experts.5.up.weight"].shape == (F, D)
    assert state["model.layers.7.block_sparse_moe.experts.5.down.weight"].shape == (D, F)
    assert state["model.layers.0.self_attn.q_proj.weight"].shape == (4 * 8, D)
    assert state["model.layers.0.self_attn.k_proj.weight"].shape == (2 * 8, D)
    assert state["model.layers.0.self_attn.o_proj.weight"].shape == (D, 4 * 8)
    assert state["lm_head.weight"].shape == (64, D)
    assert len(state) == 3 + 8 * (2 + 4 + 1 + 3 * E)
    back = fam.params_from_hf(state, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
    # a share of the experts imports its own rows of the full checkpoint
    share = dataclasses.replace(cfg, moe_first_expert=2, moe_held_experts=4)
    part = fam.params_from_hf(state, share)["layers"]["mlp"]["experts"]
    assert np.array_equal(
        np.asarray(part["down"]), np.asarray(params["layers"]["mlp"]["experts"]["down"][:, 2:6])
    )
    with pytest.raises(ValueError, match="share of a deployment"):
        fam.params_to_hf(params, share)
