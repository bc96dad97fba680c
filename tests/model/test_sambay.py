"""The decoder-hybrid-decoder stack (models/hybrid.py, the phi4flash family:
Mamba-1 mixers, window attention, ONE full-attention layer whose K and V
the cross-attention layers read, gated memory units, differential heads)
against the benchmark's plain reference, on the CPU at a tiny size: 12
layers ``[mamba1, window] x 3, mamba1, attention, [gmu, cross] x 2``, a
window of 12 positions; seeded weights, float32.  The reference
(benchmark/lib/reference_phi4flash.py) calls no model code: it is a second
implementation of the published equations, the two softmax maps of a pair
written out where the program runs zero-padded heads of twice the width.

Every tolerance here is 2e-5 on log-probabilities or logits of deviation
~0.5, float32 against float32 at "highest" precision (readings: 1e-6)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, paged
from areal_tpu.models.hf.registry import family_from_architecture, get_hf_family
from areal_tpu.ops import ssm as ssm_ops
from benchmark.lib import program as bench_program
from benchmark.lib import reference_phi4flash as ref

WINDOW = 12
HF = dict(
    architectures=["Phi4FlashForCausalLM"], model_type="phi4flash",
    hidden_size=32, num_hidden_layers=12, num_attention_heads=8,
    num_key_value_heads=4, intermediate_size=48, vocab_size=64,
    sliding_window=WINDOW, mb_per_layer=2, layer_norm_eps=1e-5,
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
    hidden_act="silu", max_position_embeddings=256,
    assumed_sizes=dict(d_state=16, d_conv=4, expand=2, dt_rank=2),
)
KINDS = ("mamba1", "window") * 3 + ("mamba1", "attention") + ("gmu", "cross") * 2
TOL = 2e-5


def make_cfg(**over):
    cfg = family_from_architecture(HF["architectures"][0]).config_from_hf(HF)
    return dataclasses.replace(cfg, dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _peaked(params):
    """The same weights with every query projection times 12: softmax maps
    that pick few positions, where random ones average their values."""
    out = jax.tree.map(lambda w: w, params)
    for stack in ("attn", "cross"):
        out[stack] = dict(out[stack], q={k: 12.0 * v for k, v in out[stack]["q"].items()})
    return out


def _forward(params, cfg, toks):
    T = len(toks)
    with jax.default_matmul_precision("highest"):
        return np.asarray(
            hybrid.forward(
                params, cfg, jnp.asarray(toks)[None], jnp.arange(T)[None],
                jnp.ones((1, T), jnp.int32),
            )[0]
        )


def test_the_config_states_the_kinds_and_what_each_layer_reads():
    cfg = make_cfg()
    assert cfg.layer_types == KINDS == tuple(ref.layer_kinds(HF))
    assert (cfg.kv_shared_layer, cfg.memory_layer) == (7, 6)
    # KV is WRITTEN by the window layers and the one attention layer
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_mamba_layers) == (4, 3, 4)
    assert (cfg.n_cross_layers, cfg.n_gmu_layers, cfg.n_global_readers) == (2, 2, 3)
    assert (cfg.norm_type, cfg.use_rope, cfg.tied_embedding) == ("layer", False, True)
    assert cfg.diff_attention and cfg.n_dense_layers == 12 and not cfg.is_moe
    # a pair is one cached head of twice the width
    assert (cfg.pool_kv_heads, cfg.pool_head_dim) == (2, 8)
    assert (cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.mamba_dt_rank) == (64, 64, 2)
    back = get_hf_family("phi4flash").config_to_hf(cfg)
    for key in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size",
        "sliding_window", "mb_per_layer", "layer_norm_eps", "model_type",
    ):
        assert back[key] == HF[key], key


def test_a_kind_the_config_does_not_know_is_refused_with_every_kind_named():
    with pytest.raises(AssertionError, match="mamba1.*gmu.*cross"):
        make_cfg(layer_types=("mamba3",) * 12)
    # two attention layers: which one would a cross layer read?
    with pytest.raises(AssertionError, match="ONE attention layer"):
        make_cfg(layer_types=("mamba1", "attention") * 4 + ("gmu", "cross") * 2)


def test_layer_plan_and_the_pools_one_global_layer_that_several_read():
    cfg = make_cfg()
    plan = hybrid.layer_plan(cfg)
    assert [(r.kind, r.first_layer, r.first_of_kind, r.count) for r in plan] == [
        ("mamba1", 0, 0, 1), ("window", 1, 0, 1), ("mamba1", 2, 1, 1),
        ("window", 3, 1, 1), ("mamba1", 4, 2, 1), ("window", 5, 2, 1),
        ("mamba1", 6, 3, 1), ("attention", 7, 3, 1), ("gmu", 8, 0, 1),
        ("cross", 9, 0, 1), ("gmu", 10, 1, 1), ("cross", 11, 1, 1),
    ]
    assert all(r.mlp == "dense" for r in plan)
    assert list(hybrid.pool_layer_numbers(cfg, "attention")) == [3]
    assert list(hybrid.pool_layer_numbers(cfg, "window")) == [0, 1, 2]
    assert paged.pool_shapes(cfg, 4, 8)[0] == (1, 4, 2, 8, 8)
    assert paged.pool_shapes(cfg, 4, 8, layers=cfg.n_window_layers)[0][0] == 3
    ssm, conv = hybrid.state_zeros(cfg, 5)
    assert ssm.shape == (4, 5, 16, 64) and conv.shape == (4, 3, 5, 64)
    assert hybrid.state_layout_bytes(cfg, 5) == ssm.nbytes + conv.nbytes


def test_init_params_has_a_stack_a_kind_and_no_expert_block(model):
    cfg, params = model
    assert set(params) == {
        "embed", "layers", "attn", "cross", "gmu", "mamba1", "dense", "final_norm",
    }
    assert set(params["layers"]) == {"attn_norm", "mlp_norm"}
    assert params["layers"]["attn_norm"]["bias"].shape == (12, 32)
    assert set(params["cross"]) == set(params["attn"]) - {"k", "v"}
    assert params["cross"]["q"]["w"].shape == (2, 32, 32)
    assert params["attn"]["k"]["b"].shape == (4, 16)
    assert params["attn"]["subln"]["scale"].shape == (4, 8)
    assert params["mamba1"]["A_log"].shape == (4, 16, 64)
    assert params["mamba1"]["x_proj"]["w"].shape == (4, 64, 2 + 32)
    assert params["gmu"]["in_proj"]["w"].shape == (2, 32, 64)
    assert params["dense"]["gate"]["w"].shape == (12, 32, 48)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_the_mamba1_mixers_three_forms_agree(model, use_kernel):
    """Whole sequence, the same split into fill chunks that carry state
    and tail (the second one padded), and one step at a time over the
    engine's slots (the kernel in interpret mode, and its jnp twin)."""
    cfg, params = model
    mp = jax.tree.map(lambda t: t[1], params["mamba1"])
    B, T, S = 2, 11, 4
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    s0 = jnp.zeros((B, 16, 64))
    tail0 = jnp.zeros((B, 3, 64))
    n = jnp.full((B,), T, jnp.int32)
    with jax.default_matmul_precision("highest"):
        out, s, tail, y = hybrid.mamba1_chunk(cfg, mp, h, n, s0, tail0)
        o1, s1, t1, y1 = hybrid.mamba1_chunk(
            cfg, mp, h[:, :4], jnp.full((B,), 4, jnp.int32), s0, tail0
        )
        rest = jnp.pad(h[:, 4:], ((0, 0), (0, 2), (0, 0)))  # 7 real of 9
        o2, s2, t2, y2 = hybrid.mamba1_chunk(
            cfg, mp, rest, jnp.full((B,), 7, jnp.int32), s1, t1
        )
        for got, want in (
            (jnp.concatenate([o1, o2[:, :7]], 1), out), (s2, s), (t2, tail),
            (jnp.concatenate([y1, y2[:, :7]], 1), y),
        ):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
        # step by step over slots 3 and 1 of 4; slots 0 and 2 stand dead
        ssm, conv = hybrid.state_zeros(cfg, S)
        ssm = ssm.at[:, 0].set(7.0)
        live = jnp.asarray([False, True, False, True])
        rows = jnp.asarray([3, 1])
        outs, ys = [], []
        for t in range(T):
            hs = jnp.zeros((S, 1, 32)).at[rows].set(h[:, t : t + 1])
            o, ssm, conv, yt = hybrid.mamba1_step(
                cfg, mp, hs, ssm, conv, 1, live, use_kernel
            )
            outs.append(o[rows])
            ys.append(yt[rows])
        assert np.abs(np.asarray(jnp.concatenate(outs, 1)) - np.asarray(out)).max() < 1e-5
        assert np.abs(np.asarray(jnp.concatenate(ys, 1)) - np.asarray(y)).max() < 1e-5
        assert np.abs(np.asarray(ssm[1, rows]) - np.asarray(s)).max() < 1e-5
        assert np.abs(np.asarray(conv[1][:, rows]).swapaxes(0, 1) - np.asarray(tail)).max() < 1e-6
        # a dead slot's state and the other layers' are as they were
        assert float(jnp.abs(ssm[1, 0] - 7.0).max()) == 0.0
        assert float(jnp.abs(ssm[0, 1:]).max()) == 0.0


def test_the_zero_padded_pair_heads_are_the_pairwise_definition(model):
    """``[q1 | 0]`` and ``[0 | q2]`` against ``[k1 | k2]`` give the two
    maps' scores, and the plain attention of those heads the two maps'
    outputs: through the pairs' difference, weight and norm it is the
    reference's differential attention, written out pair by pair."""
    cfg, params = model
    T, l, j = 20, 7, 3
    ap = jax.tree.map(lambda t: t[j], _peaked(params)["attn"])
    a = jax.random.normal(jax.random.PRNGKey(5), (T, 32))
    run = hybrid.layer_plan(cfg)[l]
    with jax.default_matmul_precision("highest"):
        want, (k_ref, v_ref) = ref._diff_attention(HF, l, False, None, a, ap)
        q, k, v = hybrid._heads_qkv(cfg, ap, a[None], jnp.arange(T)[None], run)
        assert q.shape == (1, T, 8, 8) and k.shape == v.shape == (1, T, 2, 8)
        assert float(jnp.abs(q[0, :, 0::2, 4:]).max()) == 0.0  # [q1 | 0]
        assert float(jnp.abs(q[0, :, 1::2, :4]).max()) == 0.0  # [0 | q2]
        assert np.allclose(np.asarray(k[0]).reshape(T, 2, 2, 4), np.asarray(k_ref))
        s = jnp.einsum("tgrd,ugd->grtu", q[0].reshape(T, 2, 4, 8), k[0]) / 2.0
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("grtu,ugd->tgrd", p, v[0]).reshape(1, T, -1)
        got = hybrid._heads_out(cfg, ap, l, o, jnp.float32)[0]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# 40 positions cross the window of 12 three times; 9 lie inside it
@pytest.mark.parametrize("T", [40, 9])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(T), (T,), 3, 64))
    want = np.asarray(ref.forward_logits(HF, params, toks))
    assert np.abs(_forward(params, cfg, toks) - want).max() < TOL


@pytest.fixture(scope="module")
def peaked(model):
    """Peaked weights, 40 tokens, and what the reference and the program
    say of them: the same for every mistake below."""
    cfg, params = model
    params = _peaked(params)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (40,), 3, 64))
    right = np.asarray(ref.forward_logits(HF, params, toks))
    return params, toks, right, _forward(params, cfg, toks)


@pytest.mark.parametrize("wrong", ref.WRONG[1:])
def test_each_assumed_mistake_fails_the_tolerance_at_peaked_weights(peaked, wrong):
    """The window left off, a gated memory unit fed its own input, a cross
    layer attending K and V of its own input, the second map's weight 0:
    with softmax maps that pick few positions each moves the logits by far
    more than the tolerance, while the program stays the reference."""
    params, toks, right, got = peaked
    mistaken = np.asarray(ref.forward_logits(HF, params, toks, wrong=wrong))
    assert np.abs(mistaken - right).max() > 1000 * TOL
    assert np.abs(got - right).max() < TOL


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(model):
    cfg, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (40,), 3, 64))
    right = np.asarray(ref.forward_logits(HF, params, toks))
    low = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    got = _forward(low, dataclasses.replace(cfg, dtype="bfloat16"), toks)
    assert np.abs(got.astype(np.float32) - right).max() > 100 * TOL


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


# a prompt of 29 crosses the window of 12 twice and the page of 8 three
# times; fill pieces of 5 and 13 line up with neither, so state, conv tail
# and the shared layer's pages are carried across them; 20 more tokens are
# decoded in chunks of 4 while the pages behind the window are taken out of
# the window layers' table; the kernel forms run in interpret mode
@pytest.mark.parametrize("use_kernel, piece", [(False, 16), (False, 5), (True, 13)])
def test_fill_in_chunks_then_decode_through_pools_and_slots_is_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    S, BS, MB, slot, P, W = 4, 8, 10, 2, 29, 4
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    assert k_pool.shape[0] == 1  # ONE layer, which three layers read
    win = paged.pool_zeros(cfg, 16, BS, layers=cfg.n_window_layers)
    ssm, conv = hybrid.state_zeros(cfg, S)
    ssm = ssm + 3.0  # a slot is never cleared by a pass of its own
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
            wt = wtables.copy()
            wt[0, : max(pos - WINDOW + 1, 0) // BS] = 0
            (logits, k_pool, v_pool, ssm, conv, _, routed, _,
             win) = hybrid.hybrid_fill_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
                jnp.asarray(tables), jnp.asarray([slot, 0], jnp.int32),
                use_kernel=use_kernel, win_pools=win, win_tables=jnp.asarray(wt),
            )
            assert routed is None  # no expert layer, no routing
            pos += take
        full_logits = np.asarray(ref.forward_logits(HF, params, prompt))
        assert np.abs(np.asarray(logits[0]) - full_logits[-1]).max() < TOL
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
             _, _, _, win) = hybrid.hybrid_decode_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(full), lens,
                cur, act, bud, jax.random.PRNGKey(0), W, _greedy, _never_stop,
                use_kernel=use_kernel, max_len=128, win_pools=win,
                win_tables=jnp.asarray(wfull),
            )
            e = np.asarray(em[slot])
            seq += list(np.asarray(out_t[slot])[e])
            lps += list(np.asarray(out_l[slot])[e])
    assert len(seq) == P + 21
    seq = [int(t) for t in seq]
    want = ref.sequence_logps(ref.make_token_logps(HF), params, seq, pad_to=32)
    assert np.abs(np.asarray(lps) - want[P - 1 :]).max() < TOL
    # the greedy tokens are the reference's own, so its LOGITS agree too
    logits_ref = np.asarray(ref.forward_logits(HF, params, seq[:-1]))
    assert np.array_equal(np.argmax(logits_ref[P - 1 :], -1), seq[P:])
    # the other slots' states stand as they were
    assert float(jnp.abs(ssm[:, 0] - 3.0).max()) == 0.0
    # a window off by one page is not the reference
    longer = ref.make_token_logps(dict(HF, sliding_window=WINDOW + BS))
    moved = ref.sequence_logps(longer, params, seq, pad_to=32)
    assert np.abs(moved[P - 1 :] - want[P - 1 :]).max() > 100 * TOL


def _cell_cfg(name):
    """A benchmark cell's stack as its server runs it (no weights)."""
    path = os.path.join(
        os.path.dirname(bench_program.__file__), "..", "configs", name + ".json"
    )
    with open(path) as f:
        return bench_program.model_config(json.load(f), "serve")


# (the stack, its keep-nothing tail as [(kind, first layer, every, count)
# a run] a period).  A layer that writes pages or a state, or an expert
# layer (it reports every position's routed experts), ends the tail
@pytest.mark.parametrize(
    "cfg_of, want",
    [
        (
            lambda: _cell_cfg("phi-4-mini-flash-reasoning"),
            [[("gmu", 18, 2, 7), ("cross", 19, 2, 7)]],  # layers 18-31
        ),
        (lambda: make_cfg(), [[("gmu", 8, 2, 2), ("cross", 9, 2, 2)]]),
        (lambda: _cell_cfg("granite-4.0-h-small"), []),
        (lambda: _cell_cfg("gigachat3.1-702b-a36b"), []),
        (lambda: _cell_cfg("smallthinker-21b-a3b"), []),
        # the LAST layer a cross layer with experts: not keep-nothing, and
        # the tail is a run of TRAILING periods
        (lambda: make_cfg(n_dense_layers=11), []),
        # only the trailing cross layer is behind the last layer that keeps
        (
            lambda: make_cfg(
                layer_types=KINDS[:10] + ("window", "cross"),
            ),
            [[("cross", 11, 1, 1)]],
        ),
    ],
    ids=[
        "phi4flash", "tiny", "granite", "gigachat", "smallthinker",
        "cross-with-experts-last", "window-behind-a-cross",
    ],
)
def test_the_keep_nothing_tail_follows_the_layer_kinds(cfg_of, want):
    cfg = cfg_of()
    tail = hybrid.keep_nothing_tail(cfg)
    got = [
        [(r.kind, r.first_layer, r.every, r.count) for r in period]
        for period in tail
    ]
    assert got == want
    periods = hybrid.plan_periods(cfg)
    assert tail == periods[len(periods) - len(tail):]
    assert hybrid.keep_nothing_tail_layers(cfg) == sum(
        n for period in want for _, _, _, n in period
    )


def _whole_chunk_fill(monkeypatch):
    """``hybrid_fill_chunk`` with the tail on EVERY position of the chunk,
    as every other layer runs: the program under an empty
    ``keep_nothing_tail`` (a jit of its own: the rule is read when a
    program is traced).  A test's yardstick, not a path of the program."""
    monkeypatch.setattr(hybrid, "keep_nothing_tail", lambda cfg: ())
    return jax.jit(
        hybrid.hybrid_fill_chunk.__wrapped__,
        static_argnames=("cfg", "use_kernel"),
    )


#: fill batches of [2, 16] as calls of ``(tokens already filled, tokens of
#: this chunk)`` a row, (0, 0) a padding row; prompts of 32 tokens
FILL_BATCHES = {
    # a row that ends in the middle of its chunk, a padding row beside it
    "mid-chunk+padding": [[(0, 11), (0, 0)]],
    # rows whose prefix lies in pages (two and three pages of 8: past the
    # window of 12), one ending mid-chunk and one at its chunk's end
    "prefix-in-pages": [[(0, 16), (0, 16)], [(16, 9), (16, 16)]],
    # a third chunk beside a fresh row: starts differ within the batch
    "third-chunk+fresh": [[(0, 16), (0, 0)], [(16, 8), (0, 0)], [(24, 5), (0, 13)]],
}


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("batch", list(FILL_BATCHES))
def test_a_fill_runs_the_tail_on_the_last_position_and_keeps_what_the_whole_chunk_form_kept(
    model, monkeypatch, batch, use_kernel
):
    """Last logits against ``hybrid.forward`` of the row's prompt so far;
    pools, window pools, states and conv tails EQUAL to what the program
    leaves with the tail on every position."""
    cfg, params = model
    BS, MB, C = 8, 10, 16
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 32), 3, 64))
    tables = np.zeros((2, MB), np.int32)
    tables[:, :4] = [[3, 5, 7, 9], [11, 13, 1, 2]]
    wtables = np.zeros((2, MB), np.int32)
    wtables[:, :4] = [[4, 6, 8, 10], [12, 14, 15, 1]]
    slots = jnp.asarray([2, 1], jnp.int32)

    def run(fill):
        pools = paged.pool_zeros(cfg, 16, BS)
        win = paged.pool_zeros(cfg, 16, BS, layers=cfg.n_window_layers)
        ssm, conv = hybrid.state_zeros(cfg, 4)
        ssm = ssm + 3.0  # a slot is never cleared by a pass of its own
        for call in FILL_BATCHES[batch]:
            toks = np.zeros((2, C), np.int32)
            for i, (start, take) in enumerate(call):
                toks[i, :take] = prompts[i, start : start + take]
            starts, takes = (jnp.asarray(a, jnp.int32) for a in zip(*call))
            live = np.asarray(takes) > 0
            logits, *pools, ssm, conv, _, routed, _, win = fill(
                params, *pools, ssm, conv, cfg, jnp.asarray(toks), starts, takes,
                jnp.asarray(tables * live[:, None]), slots * live,
                use_kernel=use_kernel, win_pools=win,
                win_tables=jnp.asarray(wtables * live[:, None]),
            )
            assert routed is None
        return np.asarray(logits), jax.tree.map(np.asarray, (pools, win, ssm, conv))

    with jax.default_matmul_precision("highest"):
        logits, kept = run(hybrid.hybrid_fill_chunk)
        logits_whole, kept_whole = run(_whole_chunk_fill(monkeypatch))
    for i, (start, take) in enumerate(FILL_BATCHES[batch][-1]):
        if take:
            want = _forward(params, cfg, prompts[i, : start + take])[-1]
            assert np.abs(logits[i] - want).max() < TOL, i
            assert np.abs(logits[i] - logits_whole[i]).max() < TOL, i
    assert np.isfinite(logits).all()  # a padding row's too, which nobody reads
    for got, want in zip(jax.tree.leaves(kept), jax.tree.leaves(kept_whole)):
        assert np.array_equal(got, want)
    # slot 0 is a padding row's, slot 3 nobody's
    assert np.array_equal(kept[2][:, [0, 3]], np.full_like(kept[2][:, [0, 3]], 3.0))


def test_the_float8_control_rounds_matrices_and_leaves_the_recurrence_alone(model):
    _, params = model
    # (a layer's own weights, as the reference rounds them: a vector there)
    one = jax.tree.map(lambda t: t[0], {k: params[k] for k in ("mamba1", "gmu")})
    low = ref._fp8_tree(one)
    for name in ("A_log", "D"):
        assert np.array_equal(np.asarray(low["mamba1"][name]), np.asarray(one["mamba1"][name]))
    assert not np.array_equal(
        np.asarray(low["gmu"]["in_proj"]["w"]), np.asarray(one["gmu"]["in_proj"]["w"])
    )
    toks = [int(t) for t in jax.random.randint(jax.random.PRNGKey(6), (32,), 3, 64)]
    right = ref.sequence_logps(ref.make_token_logps(HF), params, toks, pad_to=32)
    rounded = ref.sequence_logps(
        ref.make_token_logps(HF, low=("weights", "float8_e4m3fn")), params, toks,
        pad_to=32,
    )
    assert np.abs(rounded - right).max() > 100 * TOL


def test_ssm_reference_twin_forms_the_decay_tile_from_dt_and_a():
    S, N, C = 3, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (2, S, N, C))
    dt = jax.random.uniform(ks[1], (S, C), minval=0.01, maxval=0.5)
    a = -jax.random.uniform(ks[2], (N, C), minval=1.0, maxval=8.0)
    dtx, b, c = (jax.random.normal(k, s) for k, s in zip(ks[3:], ((S, C), (S, N), (S, N))))
    live = jnp.asarray([True, False, True])
    y, new = ssm_ops.ssm_state_update_reference(state, 1, dt, dtx, b, c, live, a=a)
    want = state[1] * jnp.exp(dt[:, None, :] * a[None]) + b[:, :, None] * dtx[:, None, :]
    assert np.allclose(np.asarray(new[1, 0]), np.asarray(want[0]), atol=1e-6)
    assert np.array_equal(np.asarray(new[1, 1]), np.asarray(state[1, 1]))
    assert np.allclose(np.asarray(y[2]), np.asarray(jnp.sum(want[2] * c[2][:, None], 0)), atol=1e-5)


def test_adapter_names_and_shapes_go_there_and_back(model):
    """The family's weight names (by its published modules: random weights
    have none, so names and shapes are held together here): every tensor of
    a tree exported under them comes back where it was."""
    cfg, params = model
    fam = get_hf_family("phi4flash")
    state = fam.params_to_hf(params, cfg)
    D, F, di, N, R = 32, 48, 64, 16, 2
    assert state["model.layers.0.attn.in_proj.weight"].shape == (2 * di, D)
    assert state["model.layers.0.attn.conv1d.weight"].shape == (di, 1, 4)
    assert state["model.layers.0.attn.x_proj.weight"].shape == (R + 2 * N, di)
    assert state["model.layers.0.attn.dt_proj.weight"].shape == (di, R)
    assert state["model.layers.0.attn.A_log"].shape == (di, N)
    assert state["model.layers.1.attn.Wqkv.weight"].shape == (32 + 16 + 16, D)
    assert state["model.layers.7.attn.Wqkv.bias"].shape == (64,)
    assert state["model.layers.7.attn.inner_cross_attn.subln.weight"].shape == (8,)
    assert state["model.layers.9.attn.Wqkv.weight"].shape == (32, D)  # q alone
    assert state["model.layers.9.attn.inner_cross_attn.lambda_q1"].shape == (4,)
    assert state["model.layers.8.attn.in_proj.weight"].shape == (di, D)
    assert state["model.layers.8.attn.out_proj.weight"].shape == (D, di)
    assert state["model.layers.3.mlp.fc1.weight"].shape == (2 * F, D)
    assert state["model.layers.3.mlp.fc2.weight"].shape == (D, F)
    assert "lm_head.weight" not in state  # the head is the embedding
    # embedding + final norm, 6 a layer, 9 a Mamba, 9 an attention or
    # cross layer, 2 a gated memory unit
    assert len(state) == 3 + 12 * 6 + 4 * 9 + 6 * 9 + 2 * 2
    back = fam.params_from_hf(state, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
