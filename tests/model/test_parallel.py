"""A stack of PARALLEL layers (models/hybrid.py, the falcon_h1 family:
attention and a Mamba-2 mixer side by side on one normed input in EVERY
layer, two B/C groups, muP multipliers) against the benchmark's plain
reference, on the CPU at a tiny size: 3 layers, 10 query heads on 2 KV
heads (5 a KV head) of 8, 4 Mamba heads of 8 in 2 groups with a state of 12
(not the head size); seeded weights, float32.  The reference
(benchmark/lib/reference_falcon_h1.py) calls no model code: it is a second
implementation of the published equations, the recurrence a scan over
positions with the state laid out ``[group, head, channel, state]``.

Every tolerance here is 2e-5 on logits or log-probabilities of deviation
~0.1, float32 against float32 at "highest" precision (readings: 1e-6 and
below); bfloat16 in float32's place reads 2e-3, a hundred times the
tolerance."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, paged
from areal_tpu.models.hf.registry import family_from_architecture, get_hf_family
from benchmark.lib import reference_falcon_h1 as ref

HF = dict(
    architectures=["FalconH1ForCausalLM"], model_type="falcon_h1",
    hidden_size=32, intermediate_size=64, num_hidden_layers=3,
    num_attention_heads=10, num_key_value_heads=2, head_dim=8, vocab_size=256,
    max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=1e11,
    rope_scaling=None, tie_word_embeddings=False, hidden_act="silu",
    mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=12,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, mamba_rms_norm=True,
    mamba_norm_before_gate=False, attention_bias=False, mlp_bias=False,
    projectors_bias=False,
    embedding_multiplier=5.65, lm_head_multiplier=0.5,
    attention_in_multiplier=1.0, attention_out_multiplier=0.6,
    key_multiplier=6.0, ssm_in_multiplier=0.5, ssm_out_multiplier=0.7,
    ssm_multipliers=[0.7, 1.2, 1.5, 1.3, 0.8], mlp_multipliers=[0.7, 0.3],
)
TOL = 2e-5


def make_cfg(hf=HF, **over):
    cfg = family_from_architecture(hf["architectures"][0]).config_from_hf(hf)
    return dataclasses.replace(cfg, dtype="float32", **over)


def _lively(params):
    """The same weights with a recurrence that REMEMBERS: ``dt`` around 1
    and ``A`` in (-0.5, -0.05), so a position's input stands in the state
    for tens of positions at a weight near its own (at the Mamba-2
    initialisation, ``dt`` under 0.1 and ``A`` under -1, the scan's part
    of a layer's output is a hundredth of the skip's, and a mistake in B,
    C or their groups moves the logits by less than rounding)."""
    m = dict(params["mamba"])
    u = jax.random.uniform(jax.random.PRNGKey(7), m["A_log"].shape)
    m["A_log"] = jnp.log(0.05 + 0.45 * u)
    m["dt_bias"] = 0.5 + u[::-1]
    return dict(params, mamba=m)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, _lively(hybrid.init_params(cfg, jax.random.PRNGKey(0)))


def _forward(params, cfg, toks):
    T = len(toks)
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            hybrid.forward(
                params, cfg, jnp.asarray(toks)[None], jnp.arange(T)[None],
                jnp.ones((1, T), jnp.int32),
            )[0]
        )


def _tokens(seed, n, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 3, vocab))


def test_the_config_counts_a_parallel_layer_among_both_cache_kinds():
    cfg = make_cfg()
    assert cfg.layer_types == ("parallel",) * 3
    assert (cfg.n_attn_layers, cfg.n_mamba_layers, cfg.n_parallel_layers) == (3, 3, 3)
    assert (cfg.n_window_layers, cfg.n_cross_layers, cfg.is_mamba1) == (0, 0, False)
    assert cfg.n_dense_layers == 3 and not cfg.is_moe and not cfg.tied_embedding
    assert (cfg.mamba_n_groups, cfg.mamba_d_inner, cfg.mamba_conv_dim) == (2, 32, 80)
    assert (cfg.n_q_heads // cfg.n_kv_heads, cfg.rotary_base) == (5, 1e11)
    # the head's multiplier is kept as its reciprocal; one of 1 as None
    assert cfg.logits_divisor == 2.0 and cfg.attn_in_scale is None
    assert cfg.ssm_scales == (0.7, 1.2, 1.5, 1.3, 0.8) and cfg.mlp_scales == (0.7, 0.3)
    back = get_hf_family("falcon_h1").config_to_hf(cfg)
    for key, value in HF.items():
        if key not in ("architectures", "hidden_act"):
            assert back[key] == value, key
    assert back["architectures"] == HF["architectures"]


@pytest.mark.parametrize(
    "key", ["attention_bias", "mamba_proj_bias", "mlp_bias", "mamba_norm_before_gate"]
)
def test_what_the_adapter_would_drop_is_refused_by_name(key):
    with pytest.raises(NotImplementedError, match=key.replace("mamba_norm_before_gate", "norm before")):
        make_cfg(dict(HF, **{key: True}))


def test_the_kinds_a_parallel_layer_cannot_stand_beside_are_refused():
    for other in ("latent", "mamba1"):
        with pytest.raises(AssertionError):
            make_cfg(layer_types=("parallel", "parallel", other))
    with pytest.raises(AssertionError, match="parallel"):
        make_cfg(layer_types=("both",) * 3)


def test_layer_plan_numbers_a_parallel_layer_in_both_parameter_stacks():
    cfg = make_cfg()
    (run,) = hybrid.layer_plan(cfg)
    assert (run.kind, run.mlp, run.count) == ("parallel", "dense", 3)
    assert (run.first_of_kind, run.first_of_state, run.first_in_pool) == (0, 0, 0)
    assert [list(map(int, i)) for i in hybrid._run_indices(run)] == [[0, 1, 2]] * 5
    # among other kinds each number counts what came before it
    mixed = make_cfg(
        n_layers=5, n_dense_layers=5,
        layer_types=("mamba", "parallel", "attention", "parallel", "mamba"),
    )
    plan = hybrid.layer_plan(mixed)
    assert [
        (r.kind, r.first_of_kind, r.first_of_state, r.first_in_pool) for r in plan
    ] == [
        ("mamba", 0, 0, 0), ("parallel", 0, 1, 0), ("attention", 1, 0, 1),
        ("parallel", 2, 2, 2), ("mamba", 3, 0, 1),
    ]
    assert (mixed.n_attn_layers, mixed.n_mamba_layers) == (3, 4)
    assert list(hybrid.pool_layer_numbers(mixed, "attention")) == [0, 1, 2]


def test_pages_and_state_slots_for_every_layer():
    cfg = make_cfg()
    assert paged.pool_shapes(cfg, 4, 8)[0] == (3, 4, 2, 8, 8)
    ssm, conv = hybrid.state_zeros(cfg, 5)
    assert ssm.shape == (3, 5, 12, 32) and conv.shape == (3, 3, 5, 80)
    assert hybrid.state_layout_bytes(cfg, 5) == ssm.nbytes + conv.nbytes


def test_init_params_has_both_mixers_stacks_and_an_untied_head(model):
    cfg, params = model
    assert set(params) == {
        "embed", "layers", "attn", "mamba", "dense", "final_norm", "lm_head",
    }
    assert set(params["layers"]) == {"attn_norm", "mlp_norm"}
    assert params["attn"]["q"]["w"].shape == (3, 32, 80)
    assert params["attn"]["k"]["w"].shape == (3, 32, 16)
    # [z 32 | x 32 | B 2 x 12 | C 2 x 12 | dt 4]
    assert params["mamba"]["in_proj"]["w"].shape == (3, 32, 32 + 80 + 4)
    assert params["mamba"]["conv"]["w"].shape == (3, 4, 80)
    assert params["mamba"]["norm"]["scale"].shape == (3, 32)
    assert params["dense"]["gate"]["w"].shape == (3, 32, 64)
    assert params["lm_head"]["w"].shape == (32, 256)


def test_a_multiplied_matrix_is_drawn_wider_by_its_multiplier():
    """Seeded weights: ``m`` times a matrix that the published multiplier
    ``m`` scales is the matrix a stack WITHOUT multipliers draws from the
    same seed (so the multiplied products have the deviations they have
    in every other stack), and what no multiplier scales is the same
    draw."""
    cfg = make_cfg()
    plain = dataclasses.replace(
        cfg, embed_scale=None, logits_divisor=None, attn_in_scale=None,
        attn_out_scale=None, key_scale=None, ssm_in_scale=None,
        ssm_out_scale=None, ssm_scales=None, mlp_scales=None,
    )
    key = jax.random.PRNGKey(3)
    got, want = hybrid.init_params(cfg, key), hybrid.init_params(plain, key)
    seg = np.repeat(HF["ssm_multipliers"], (32, 32, 24, 24, 4))
    factors = {
        ("embed", "weight"): HF["embedding_multiplier"],
        ("lm_head", "w"): HF["lm_head_multiplier"],
        ("attn", "k", "w"): HF["key_multiplier"],
        ("attn", "o", "w"): HF["attention_out_multiplier"],
        ("mamba", "in_proj", "w"): HF["ssm_in_multiplier"] * seg,
        ("mamba", "out_proj", "w"): HF["ssm_out_multiplier"],
        ("dense", "gate", "w"): HF["mlp_multipliers"][0],
        ("dense", "down", "w"): HF["mlp_multipliers"][1],
    }
    leaves = jax.tree_util.tree_flatten_with_path(got)[0]
    for path, leaf in leaves:
        names = tuple(p.key for p in path)
        other = want
        for name in names:
            other = other[name]
        np.testing.assert_allclose(
            np.asarray(leaf) * factors.pop(names, 1.0), np.asarray(other),
            rtol=1e-5, atol=1e-7, err_msg=str(names),
        )
    assert not factors, factors


def test_hf_names_round_trip_through_the_adapter(model):
    cfg, params = model
    fam = get_hf_family("falcon_h1")
    state = fam.params_to_hf(params, cfg)
    per_layer = {
        "input_layernorm.weight", "pre_ff_layernorm.weight",
        *(f"self_attn.{n}_proj.weight" for n in "qkvo"),
        "mamba.in_proj.weight", "mamba.conv1d.weight", "mamba.conv1d.bias",
        "mamba.dt_bias", "mamba.A_log", "mamba.D", "mamba.norm.weight",
        "mamba.out_proj.weight",
        *(f"feed_forward.{n}_proj.weight" for n in ("gate", "up", "down")),
    }
    assert set(state) == {
        "model.embed_tokens.weight", "model.final_layernorm.weight",
        "lm_head.weight",
    } | {f"model.layers.{i}.{n}" for i in range(3) for n in per_layer}
    # torch's layouts: [out, in] matrices, a depthwise conv [cd, 1, K]
    assert state["model.layers.1.mamba.in_proj.weight"].shape == (116, 32)
    assert state["model.layers.1.mamba.conv1d.weight"].shape == (80, 1, 4)
    assert state["lm_head.weight"].shape == (256, 32)
    back = fam.params_from_hf(state, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T", [37])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = _tokens(T, T)
    want = np.asarray(ref.forward_logits(HF, params, toks))
    assert np.abs(want).max() > 0.1
    assert np.abs(_forward(params, cfg, toks) - want).max() < TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(model):
    cfg, params = model
    toks = _tokens(4, 37)
    right = np.asarray(ref.forward_logits(HF, params, toks))
    low = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    got = _forward(low, dataclasses.replace(cfg, dtype="bfloat16"), toks)
    assert np.abs(got.astype(np.float32) - right).max() > 50 * TOL


#: every multiplier of the published config, each moved alone
MOVED = [
    ("embedding_multiplier", 4.0), ("lm_head_multiplier", 0.25),
    ("attention_in_multiplier", 1.5), ("attention_out_multiplier", 0.9),
    ("key_multiplier", 3.0), ("ssm_in_multiplier", 0.8),
    ("ssm_out_multiplier", 0.4),
    *((f"ssm_multipliers.{i}", 4.0 if i == 4 else 0.5) for i in range(5)),
    *((f"mlp_multipliers.{i}", 1.1) for i in range(2)),
]


def _moved(hf, name, value):
    key, _, at = name.partition(".")
    out = dict(hf)
    if at:
        out[key] = list(hf[key])
        out[key][int(at)] = value
    else:
        out[key] = value
    return out


@pytest.fixture(scope="module")
def base_logits(model):
    toks = _tokens(6, 11)
    return toks, np.asarray(ref.forward_logits(HF, model[1], toks))


@pytest.mark.parametrize("name, value", MOVED, ids=[n for n, _ in MOVED])
def test_no_multiplier_is_dead_in_the_reference(model, base_logits, name, value):
    """Moved alone, each of the fourteen changes the reference's logits
    by a hundred tolerances or more."""
    toks, base = base_logits
    want = np.asarray(ref.forward_logits(_moved(HF, name, value), model[1], toks))
    assert np.abs(want - base).max() > 100 * TOL


def test_every_multiplier_sits_where_the_reference_has_it(model, base_logits):
    """With all fourteen moved at once, each to a value of its own, the
    program is still the reference: none is dead in the program either
    (each alone moves the reference, the test above), and none stands in
    another's place."""
    toks, base = base_logits
    hf = HF
    for i, (name, value) in enumerate(MOVED):
        hf = _moved(hf, name, value * (1.0 + 0.03 * i))
    want = np.asarray(ref.forward_logits(hf, model[1], toks))
    assert np.abs(want - base).max() > 100 * TOL
    assert np.abs(_forward(model[1], make_cfg(hf), toks) - want).max() < TOL


def test_a_sliced_vocabulary_gives_the_uncut_logits_over_its_rows(model):
    """The program at an eighth of the rows (embedding and head cut, ids
    from the slice) against the UNCUT reference over rows 0..V/8."""
    cfg, params = model
    V = cfg.vocab_size // 8
    toks = _tokens(8, 29, vocab=V)
    cut = dict(
        params, embed={"weight": params["embed"]["weight"][:V]},
        lm_head={"w": params["lm_head"]["w"][:, :V]},
    )
    got = _forward(cut, dataclasses.replace(cfg, vocab_size=V), toks)
    want = np.asarray(ref.forward_logits(HF, params, toks))
    assert got.shape == (29, V) and np.abs(got - want[:, :V]).max() < TOL
    # the adapter takes the held rows of a full checkpoint
    fam = get_hf_family("falcon_h1")
    sliced = fam.params_from_hf(
        fam.params_to_hf(params, cfg), dataclasses.replace(cfg, vocab_size=V)
    )
    assert sliced["embed"]["weight"].shape == (V, 32)
    assert np.array_equal(
        np.asarray(sliced["lm_head"]["w"]), np.asarray(cut["lm_head"]["w"])
    )


@pytest.mark.parametrize("use_kernel", [False, True])
def test_the_mamba2_mixers_three_forms_agree_with_two_groups(model, use_kernel):
    """Whole sequence, the same split into fill chunks that carry state
    and tail (the second one padded), and one step at a time over the
    engine's slots (the kernel in interpret mode, and its jnp twin)."""
    cfg, params = model
    mp = jax.tree.map(lambda t: t[1], params["mamba"])
    B, T, S = 2, 19, 4
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    s0 = jnp.zeros((B, 12, 32))
    tail0 = jnp.zeros((B, 3, 80))
    chunk = jax.jit(
        lambda mp, h, n, s0, tail0: hybrid.mamba_chunk(
            cfg, mp, h, jnp.full((B,), n, jnp.int32), s0, tail0
        ),
        static_argnums=2,
    )
    with jax.default_matmul_precision("highest"):
        out, s, tail = chunk(mp, h, T, s0, tail0)
        o1, s1, t1 = chunk(mp, h[:, :6], 6, s0, tail0)
        rest = jnp.pad(h[:, 6:], ((0, 0), (0, 3), (0, 0)))  # 13 real of 16
        o2, s2, t2 = chunk(mp, rest, 13, s1, t1)
        for got, want in (
            (jnp.concatenate([o1, o2[:, :13]], 1), out), (s2, s), (t2, tail),
        ):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
        # step by step over slots 3 and 1 of 4; slots 0 and 2 stand dead
        ssm, conv = hybrid.state_zeros(cfg, S)
        ssm = ssm.at[:, 0].set(7.0)
        live = jnp.asarray([False, True, False, True])
        rows = jnp.asarray([3, 1])
        outs = []
        step = jax.jit(
            lambda hs, ssm, conv: hybrid.mamba_step(
                cfg, mp, hs, ssm, conv, 1, live, use_kernel
            )
        )
        for t in range(T):
            hs = jnp.zeros((S, 1, 32)).at[rows].set(h[:, t : t + 1])
            o, ssm, conv = step(hs, ssm, conv)
            outs.append(o[rows])
        assert np.abs(np.asarray(jnp.concatenate(outs, 1)) - np.asarray(out)).max() < 1e-5
        assert np.abs(np.asarray(ssm[1, rows]) - np.asarray(s)).max() < 1e-5
        assert np.abs(np.asarray(conv[1][:, rows]).swapaxes(0, 1) - np.asarray(tail)).max() < 1e-6
        # a dead slot's state and the other layers' are as they were
        assert float(jnp.abs(ssm[1, 0] - 7.0).max()) == 0.0
        assert float(jnp.abs(ssm[0, 1:]).max()) == 0.0
    # group 1's B and C are read by heads 2 and 3 alone: with group 0's
    # in their place the mixer's output moves
    w = mp["in_proj"]["w"]
    swapped = w.at[:, 64 + 12 : 64 + 24].set(w[:, 64 : 64 + 12])
    with jax.default_matmul_precision("highest"):
        other, _, _ = chunk(dict(mp, in_proj={"w": swapped}), h, T, s0, tail0)
    assert np.abs(np.asarray(other) - np.asarray(out)).max() > 1e-3


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


def _fill_then_decode(cfg, params, prompt, piece, use_kernel, new=21):
    """The prompt through ``hybrid_fill_chunk`` in pieces of ``piece``
    (row 0 of 2, slot 2 of 4, scattered pages of 8), then tokens through
    ``hybrid_decode_chunk`` in chunks of 4 until ``new`` are made (the
    fill's first among them).  Returns ``(last
    prompt logits, sequence, its new tokens' log-probabilities, ssm)``."""
    S, BS, MB, slot, W = 4, 8, 10, 2, 4
    P = len(prompt)
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    ssm, conv = hybrid.state_zeros(cfg, S)
    ssm = ssm + 3.0  # a slot is never cleared by a pass of its own
    tables = np.zeros((2, MB), np.int32)
    tables[0, :8] = [3, 5, 7, 9, 11, 13, 1, 2]
    with jax.default_matmul_precision("highest"):
        pos = 0
        while pos < P:
            take = min(piece, P - pos)
            toks = np.zeros((2, 16), np.int32)
            toks[0, :take] = prompt[pos : pos + take]
            (logits, k_pool, v_pool, ssm, conv, _, routed,
             _) = hybrid.hybrid_fill_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
                jnp.asarray(tables), jnp.asarray([slot, 0], jnp.int32),
                use_kernel=use_kernel,
            )
            assert routed is None  # no expert layer, no routing
            pos += take
        lp0 = jax.nn.log_softmax(logits[0])
        first = int(jnp.argmax(lp0))
        full = np.zeros((S, MB), np.int32)
        full[slot] = tables[0]
        onehot = np.arange(S) == slot
        lens = jnp.asarray(np.where(onehot, P, 0), jnp.int32)
        cur = jnp.asarray(np.where(onehot, first, 0), jnp.int32)
        act = jnp.asarray(onehot)
        bud = jnp.asarray(np.where(onehot, new - 1, 0), jnp.int32)
        seq, lps = list(prompt) + [first], [float(lp0[first])]
        for _ in range(-(-(new - 1) // W)):
            (k_pool, v_pool, ssm, conv, lens, out_t, out_l, em, cur, act, bud,
             _, _, _) = hybrid.hybrid_decode_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(full), lens,
                cur, act, bud, jax.random.PRNGKey(0), W, _greedy, _never_stop,
                use_kernel=use_kernel, max_len=128,
            )
            e = np.asarray(em[slot])
            seq += list(np.asarray(out_t[slot])[e])
            lps += list(np.asarray(out_l[slot])[e])
    return np.asarray(logits[0]), [int(t) for t in seq], np.asarray(lps), ssm


# a prompt of 29 crosses the page of 8 three times and the SSD chunk of 8
# likewise; fill pieces of 5 and 13 line up with neither, so state, conv
# tail and pages are carried across them; 21 more tokens are decoded in
# chunks of 4; the kernel forms (paged attention, the state update with two
# groups, the state rows) run in interpret mode
@pytest.mark.parametrize("use_kernel, piece", [(False, 5), (True, 13)])
def test_fill_in_chunks_then_decode_through_pages_and_slots_is_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    prompt = _tokens(1, 29)
    P = len(prompt)
    logits, seq, lps, ssm = _fill_then_decode(cfg, params, prompt, piece, use_kernel)
    full_logits = np.asarray(ref.forward_logits(HF, params, prompt))
    assert np.abs(logits - full_logits[-1]).max() < TOL
    assert len(seq) == P + 21
    want = ref.sequence_logps(ref.make_token_logps(HF), params, seq, pad_to=32)
    assert np.abs(lps - want[P - 1 :]).max() < TOL
    # the greedy tokens are the reference's own, so its LOGITS agree too
    logits_ref = np.asarray(ref.forward_logits(HF, params, seq[:-1]))
    assert np.array_equal(np.argmax(logits_ref[P - 1 :], -1), seq[P:])
    # the other slots' states stand as they were
    assert float(jnp.abs(ssm[:, 0] - 3.0).max()) == 0.0
    # the float8 control and a state carried in bfloat16, as the cell's
    # check runs them: the first is far outside the tolerance
    low = ref.sequence_logps(
        ref.make_token_logps(HF, low=("weights", "float8_e4m3fn")), params, seq,
        pad_to=32,
    )
    assert np.abs(low[P - 1 :] - want[P - 1 :]).max() > 100 * TOL
    kept = ref.sequence_logps(
        ref.make_token_logps(HF, low=("state", "bfloat16")), params, seq, pad_to=32
    )
    assert np.isfinite(kept).all()


def test_a_parallel_layer_among_other_kinds_reads_its_own_numbers():
    """``[mamba, parallel, attention, parallel, mamba]``: a parallel
    layer's attention mixer is the first or third of ``params["attn"]``
    and its Mamba mixer the second or third of ``params["mamba"]``; fill
    pieces and decode steps agree with the whole-sequence form."""
    cfg = make_cfg(
        n_layers=5, n_dense_layers=5,
        layer_types=("mamba", "parallel", "attention", "parallel", "mamba"),
    )
    params = _lively(hybrid.init_params(cfg, jax.random.PRNGKey(3)))
    assert params["attn"]["q"]["w"].shape[0] == 3
    assert params["mamba"]["in_proj"]["w"].shape[0] == 4
    prompt = _tokens(2, 21)
    logits, seq, lps, _ = _fill_then_decode(cfg, params, prompt, 8, False, new=6)
    whole = _forward(params, cfg, np.asarray(seq[:-1]))
    assert np.abs(logits - whole[len(prompt) - 1]).max() < TOL
    want = np.take_along_axis(
        np.asarray(jax.nn.log_softmax(whole[len(prompt) - 1 :])),
        np.asarray(seq[len(prompt) :])[:, None], -1,
    )[:, 0]
    assert np.abs(lps - want).max() < TOL
