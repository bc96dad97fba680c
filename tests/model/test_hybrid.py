"""A stack stated by kind (models/hybrid.py, the granitemoehybrid family)
against the benchmark's plain reference, on the CPU at a tiny size: 2
periods of a short pattern, 8 experts top 3, one shared; seeded weights,
float32.  The reference (benchmark/lib/reference_granitemoehybrid.py)
calls no model code: it is a second implementation of the published
equations."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, moe, paged
from areal_tpu.models.hf.registry import family_from_architecture
from benchmark.lib import reference_granitemoehybrid as ref

PATTERN = ["mamba", "mamba", "attention", "mamba"] * 2
HF = dict(
    architectures=["GraniteMoeHybridForCausalLM"], hidden_size=32,
    intermediate_size=16, shared_intermediate_size=24, num_hidden_layers=8,
    layer_types=PATTERN, num_attention_heads=4, num_key_value_heads=2,
    num_local_experts=8, num_experts_per_tok=3, mamba_n_heads=4,
    mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1,
    mamba_chunk_size=8, attention_multiplier=0.2, embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=2.0, rms_norm_eps=1e-5,
    tie_word_embeddings=True, vocab_size=64,
)


def make_cfg(**over):
    cfg = family_from_architecture(HF["architectures"][0]).config_from_hf(HF)
    return dataclasses.replace(cfg, dtype="float32", **over)


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _reference(params, seq, first=0):
    fn = ref.make_token_logps(HF, first_expert=first)
    return ref.sequence_logps(fn, params, [int(t) for t in seq], pad_to=32)[0]


def test_layer_plan_cuts_the_published_order_into_runs_of_one_kind():
    plan = hybrid.layer_plan(make_cfg())
    assert [(r.kind, r.first_layer, r.first_of_kind, r.count) for r in plan] == [
        ("mamba", 0, 0, 2), ("attention", 2, 0, 1), ("mamba", 3, 2, 3),
        ("attention", 6, 1, 1), ("mamba", 7, 5, 1),
    ]


# T 21 and 8 do and do not divide by the SSD chunk of 8
@pytest.mark.parametrize("T", [21, 8, 3])
def test_whole_sequence_forward_is_the_reference(model, T):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(T), (1, T), 3, 64)
    pos = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        got = hybrid.logprobs_of_labels(
            params, cfg, toks, pos, jnp.ones((1, T), jnp.int32)
        )[0]
    want = _reference(params, np.asarray(toks[0]))
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def test_padding_after_a_sequence_does_not_reach_it(model):
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 3, 64)
    pos = jnp.arange(24)[None]
    seg = (jnp.arange(24) < 13)[None].astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = hybrid.logprobs_of_labels(params, cfg, toks, pos, seg)[0, :12]
    want = _reference(params, np.asarray(toks[0, :13]))
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


# fill pieces of 5 do not line up with the SSD chunk of 8, nor with the
# page of 8; the kernel forms run in interpret mode
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("piece", [5, 8, 13])
def test_fill_in_chunks_then_decode_through_slots_and_pages_is_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    S, BS, MB, slot = 4, 8, 8, 2
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    assert k_pool.shape[0] == cfg.n_attn_layers == 2  # pages of attention layers only
    ssm, conv = hybrid.state_zeros(cfg, S)
    ssm = ssm + 7.0  # a slot is dirty when a fill takes it
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
             r, rounds) = hybrid.hybrid_fill_chunk(
                params, k_pool, v_pool, ssm, conv, cfg, jnp.asarray(toks),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray([take, 0], jnp.int32),
                jnp.asarray(tables), jnp.asarray([slot, 0], jnp.int32),
                use_kernel=use_kernel,
            )
            # every valid token routed top-3 in each of 8 layers
            assert int(pairs.sum()) == take * 3 * 8
            # no count of rounds comes out of (or rides the loops of) a
            # program whose experts multiply every held expert
            assert rounds is None
            routed.append(np.asarray(r)[:, 0, :take].swapaxes(0, 1))
            pos += take
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
            # [W, L, K, B] -> this row's emitted steps as [n, L, K]
            routed.append(np.asarray(r)[e, :, :, slot])
    assert len(seq) == 13 + 10
    # the other slots were never touched
    assert float(jnp.abs(ssm[:, [0, 1, 3]] - 7.0).max()) == 0.0
    want = _reference(params, seq)[12:]
    assert np.abs(np.asarray(lps) - want).max() < 2e-5
    # the routing the two programs hand out: one entry a position READ
    # (every one but the last), the reference's own choices at float32,
    # and a reference that FOLLOWS them says the same
    routed = np.concatenate(routed)
    assert routed.shape == (len(seq) - 1, 8, 3)
    fn = ref.make_token_logps(HF)
    followed, _, flips = ref.sequence_logps(
        fn, params, [int(t) for t in seq], routed=routed, pad_to=32
    )
    assert int(flips.sum()) == 0
    assert np.abs(followed[12:] - want).max() < 1e-6
    # ... and one that follows OTHER choices does not
    other = (routed + 1) % 8
    moved, _, flips = ref.sequence_logps(
        fn, params, [int(t) for t in seq], routed=other, pad_to=32
    )
    assert int(flips.min()) == 8 and np.abs(moved[12:] - want).max() > 1e-4


@pytest.mark.parametrize("n_tokens", [6, 40])
def test_shares_of_a_stated_split_add_up_to_the_uncut_layer(n_tokens):
    """2 chips x 4 of 8 experts: what each share gives, with the shared
    expert (which every chip computes alike) counted once, is the uncut
    layer of the reference.  One form of the held-expert compute, in one
    call and cut into pieces of a few tokens."""
    whole = make_cfg()
    params = hybrid.init_params(whole, jax.random.PRNGKey(3))
    lp = jax.tree.map(lambda a: a[1], params["layers"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(4), (1, n_tokens, 32))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._experts(HF, m[0], lp, first=0)
        no_shared = {k: v for k, v in lp.items() if k != "shared"}
        total, pairs = 0.0, []
        for first in (0, 4):
            cfg = make_cfg(moe_first_expert=first, moe_held_experts=4)
            share = dict(
                no_shared,
                experts=jax.tree.map(lambda a: a[first : first + 4], lp["experts"]),
            )
            for limit in (1024, 4):  # one call; pieces of 4 tokens
                old, moe.DENSE_EXPERTS_CALL_TOKENS = moe.DENSE_EXPERTS_CALL_TOKENS, limit
                try:
                    out, p, idx, _ = moe.held_moe_mlp(cfg, m, share)
                finally:
                    moe.DENSE_EXPERTS_CALL_TOKENS = old
                if limit == 1024:
                    total, whole_call = total + out[0], out[0]
                    pairs.append(p)
                else:
                    assert np.abs(np.asarray(out[0] - whole_call)).max() < 1e-5
                assert idx.shape == (1, n_tokens, whole.n_experts_per_tok)
        cfg = make_cfg(moe_first_expert=0, moe_held_experts=0)
        shared_only, _, _, _ = moe.held_moe_mlp(
            cfg, m, dict(lp, experts=jax.tree.map(lambda a: a[:0], lp["experts"]))
        )
    assert np.abs(np.asarray(total + shared_only[0] - want)).max() < 1e-5
    # each share counts its own pairs and, last, those routed elsewhere
    assert int(pairs[0][:-1].sum()) == int(pairs[1][-1])
    assert int(pairs[0].sum()) == n_tokens * 3


def test_config_round_trips_through_the_hf_keys():
    fam = family_from_architecture("GraniteMoeHybridForCausalLM")
    cfg = fam.config_from_hf(HF)
    assert cfg.layer_types == tuple(PATTERN) and not cfg.use_rope
    assert (cfg.embed_scale, cfg.residual_scale, cfg.attention_scale,
            cfg.logits_divisor) == (3.0, 0.5, 0.2, 2.0)
    assert cfg.n_attn_layers == 2 and cfg.n_mamba_layers == 6
    assert fam.config_from_hf(fam.config_to_hf(cfg)) == cfg


@pytest.mark.parametrize("name", ["position_embedding_type", "mamba_n_groups",
                                  "attention_bias"])
def test_config_refuses_what_the_stack_does_not_write(name):
    fam = family_from_architecture("GraniteMoeHybridForCausalLM")
    bad = {"position_embedding_type": "rope", "mamba_n_groups": 2,
           "attention_bias": True}[name]
    with pytest.raises(NotImplementedError):
        fam.config_from_hf(dict(HF, **{name: bad}))


def test_parameter_maps_round_trip_on_a_synthetic_state_dict(model):
    cfg, params = model
    fam = family_from_architecture("GraniteMoeHybridForCausalLM")
    state = fam.params_to_hf(params, cfg)
    # HF's own shapes: input_linear [E, 2F, D], conv1d [conv_dim, 1, K]
    assert state["model.layers.0.block_sparse_moe.input_linear.weight"].shape == (8, 32, 32)
    assert state["model.layers.0.mamba.conv1d.weight"].shape == (64, 1, 4)
    assert state["model.layers.0.mamba.in_proj.weight"].shape == (32 + 64 + 4, 32)
    assert "model.layers.2.self_attn.q_proj.weight" in state
    assert "model.layers.2.mamba.in_proj.weight" not in state
    back = fam.params_from_hf(state, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the round trip is the same MODEL, not only the same numbers
    seq = np.arange(3, 20)
    assert np.abs(_reference(back, seq) - _reference(params, seq)).max() == 0.0


def test_a_share_imports_its_own_experts_and_cannot_be_exported(model):
    cfg, params = model
    fam = family_from_architecture("GraniteMoeHybridForCausalLM")
    state = fam.params_to_hf(params, cfg)
    share = dataclasses.replace(cfg, moe_first_expert=2, moe_held_experts=4)
    held = fam.params_from_hf(state, share)
    ex = held["layers"]["mlp"]["experts"]
    assert ex["gate"].shape == (8, 4, 16, 32)
    assert np.array_equal(
        np.asarray(ex["down"]),
        np.asarray(params["layers"]["mlp"]["experts"]["down"][:, 2:6]),
    )
    with pytest.raises(ValueError, match="share"):
        fam.params_to_hf(held, share)
