"""The held experts' two products (``models/moe.py``): a fill multiplies
the (token, k) pairs its router chose, grouped by expert, and gives what
the product over every held expert gives; a decode step's call is that
product, as it was."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import moe
from areal_tpu.models.config import TransformerConfig

D, F = 32, 16


def _cfg(**kw):
    base = dict(
        n_layers=1, hidden_dim=D, n_q_heads=2, n_kv_heads=2, head_dim=16,
        intermediate_dim=F, moe_intermediate_dim=F, vocab_size=64,
        dtype="float32",
    )
    return TransformerConfig(**{**base, **kw})


#: a Mamba layer in the stack: the facts by which ``moe.group_rows`` knows
#: a stack with recurrent state
RECURRENT = dict(
    n_layers=2, layer_types=("mamba", "attention"), mamba_n_heads=2,
    mamba_head_dim=8, mamba_d_state=8,
)

#: the three stacks' routers and held sets, at their published counts
STACKS = {
    # granite-4.0-h-small: 36 of 72 held, top 10, a shared expert (here
    # WITHOUT its Mamba layers: with them no call takes the grouped form)
    "hybrid": dict(
        n_experts=72, n_experts_per_tok=10, moe_router="topk_softmax",
        moe_held_experts=36, shared_expert_dim=24,
    ),
    # gigachat3.1: experts 32-47 of 256 held (pairs below AND above the
    # held range), group-limited sigmoid router with a choice bias, top 8
    "latent": dict(
        n_experts=256, n_experts_per_tok=8, moe_router="sigmoid_group",
        moe_n_groups=8, moe_topk_groups=4, moe_routed_scale=2.5,
        moe_held_experts=16, moe_first_expert=32, shared_expert_dim=16,
    ),
    # smallthinker: all 64 held, top 6, ReLU gate, the router reads the
    # mixer's input
    "window": dict(
        n_experts=64, n_experts_per_tok=6, moe_router="topk_softmax",
        moe_router_input="attn", activation="relu",
    ),
}


def _layer(cfg, key):
    ks = jax.random.split(key, 8)
    held, E = cfg.n_held_experts, cfg.n_experts
    p = {
        "router": {"w": jax.random.normal(ks[0], (D, E)) / np.sqrt(D)},
        "experts": {
            "gate": jax.random.normal(ks[1], (held, F, D)) / np.sqrt(D),
            "up": jax.random.normal(ks[2], (held, F, D)) / np.sqrt(D),
            "down": jax.random.normal(ks[3], (held, D, F)) / np.sqrt(F),
        },
    }
    p["experts"]["down"] = jnp.swapaxes(p["experts"]["down"], 1, 2)  # [E, F, D]
    if cfg.moe_router == "sigmoid_group":
        p["router"]["bias"] = jax.random.uniform(ks[4], (E,), minval=-0.15, maxval=0.15)
    if cfg.shared_expert_dim:
        S = cfg.shared_expert_dim
        p["shared"] = {
            "gate": jax.random.normal(ks[5], (D, S)) / np.sqrt(D),
            "up": jax.random.normal(ks[6], (D, S)) / np.sqrt(D),
            "down": jax.random.normal(ks[7], (S, D)) / np.sqrt(S),
        }
    return p


def _every_held_expert(cfg, h, p, router_input):
    """``held_moe_mlp``'s result by :func:`moe.dense_expert_compute`,
    whatever the call's length."""
    x = h.reshape(-1, D)
    on = x if router_input is None else router_input.reshape(-1, D)
    w, idx, _, _ = moe.route(cfg, on, p["router"])
    local = idx - cfg.moe_first_expert
    w_tok = jnp.sum(
        jnp.where(
            local[:, :, None] == jnp.arange(cfg.n_held_experts)[None, None, :],
            w[:, :, None], 0.0,
        ),
        axis=1,
    )
    ex = p["experts"]
    out = moe.dense_expert_compute(
        x, w_tok, ex["gate"], ex["up"], ex["down"], cfg.activation
    )
    if "shared" in p:
        sh = p["shared"]
        act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[cfg.activation]
        out = out + (act(x @ sh["gate"]) * (x @ sh["up"])) @ sh["down"]
    return out.reshape(h.shape)


def _stacked(cfg, n_layers):
    """``n_layers`` layers' parameters stacked, as ``hybrid._mlp_half``
    hands them over."""
    layers = [_layer(cfg, jax.random.PRNGKey(7 + i)) for i in range(n_layers)]
    return layers, jax.tree.map(lambda *a: jnp.stack(a), *layers)


# name: (stack, [B, T], padding under ``valid``, what is done to the router);
# "stacked-" cases hand over a STACK of three layers and ``layer=``, as the
# served programs do, so the round slices the stack inside its loop
CASES = {
    "hybrid-36-of-72-top-10": ("hybrid", (1, 1100), False, None),
    "latent-16-of-256-held-elsewhere": ("latent", (4, 256), False, None),
    "window-64-of-64-router-input": ("window", (1, 1024), False, None),
    "padding-tokens-take-no-room": ("window", (4, 256), True, None),
    "latent-padding-and-held-elsewhere": ("latent", (4, 256), True, None),
    "one-expert-over-a-groups-rows": ("window", (1, 1024), False, "skew"),
    "an-expert-with-no-pair": ("hybrid", (1, 1100), False, "starve"),
    "a-call-longer-than-one-piece": ("window", (1, 2500), False, None),
    "a-long-call-mostly-padding": ("window", (4, 1024), True, None),
    "stacked-window-layer-of-three": ("window", (1, 1024), False, None),
    "stacked-latent-padding-and-held-elsewhere": ("latent", (4, 256), True, None),
    "stacked-one-expert-over-a-groups-rows": ("window", (1, 1024), False, "skew"),
    "stacked-a-call-longer-than-one-piece": ("hybrid", (1, 2500), False, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_product_is_the_product_over_every_held_expert(case):
    stack, (B, T), padded, twist = CASES[case]
    cfg = _cfg(**STACKS[stack])
    p = _layer(cfg, jax.random.PRNGKey(7))
    stacked = case.startswith("stacked-")
    if stacked:
        layers, _ = _stacked(cfg, 3)
        p = layers[1]
    if twist == "skew":
        # every token's first choice is expert 5: it holds B x T pairs
        p["router"]["w"] = p["router"]["w"].at[:, 5].set(0.0)
        shift = jnp.zeros((cfg.n_experts,)).at[5].set(50.0)
    elif twist == "starve":
        shift = jnp.zeros((cfg.n_experts,)).at[3].set(-50.0)
    kh, ka, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    h = jax.random.normal(kh, (B, T, D))
    a = jax.random.normal(ka, (B, T, D)) if cfg.moe_router_input == "attn" else None
    if twist:
        # a constant input column carries the shift into the logits
        h = h.at[..., 0].set(1.0)
        a = None if a is None else a.at[..., 0].set(1.0)
        p["router"]["w"] = p["router"]["w"].at[0].set(shift)
    valid = None
    if padded:
        # rows filled to different lengths, one of them empty
        lens = jnp.asarray([T, T // 3, 0, 7][:B])
        valid = jnp.arange(T)[None, :] < lens[:, None]
    cap = moe.group_rows(cfg, B * T)
    assert cap == moe.GROUP_ROWS
    with jax.default_matmul_precision("highest"):
        if stacked:
            layers[1] = p  # with the router's twist
            stack = jax.tree.map(lambda *a: jnp.stack(a), *layers)
            got, pairs, idx, rounds = jax.jit(
                lambda h, a, l: moe.held_moe_mlp(
                    cfg, h, stack, valid=valid, router_input=a, layer=l
                )
            )(h, a, jnp.int32(1))
        else:
            got, pairs, idx, rounds = jax.jit(
                lambda h, a: moe.held_moe_mlp(
                    cfg, h, p, valid=valid, router_input=a
                )
            )(h, a)
        want = _every_held_expert(cfg, h, p, a)
    keep = np.ones((B, T), bool) if valid is None else np.asarray(valid)
    assert np.abs(np.asarray(got - want))[keep].max() < 1e-5
    # the rounds follow the busiest held expert's pairs, and no pair is lost
    held = cfg.n_held_experts
    busiest = int(pairs[:held].max())
    assert int(rounds) == max(-(-busiest // cap) - 1, 0)
    local = np.asarray(idx) - cfg.moe_first_expert
    counted = np.bincount(
        local[keep][(local[keep] >= 0) & (local[keep] < held)], minlength=held
    )
    assert np.array_equal(np.asarray(pairs[:held]), counted)
    if twist == "skew":
        assert busiest == B * T and int(rounds) == -(-B * T // cap) - 1 > 0
    if twist == "starve":
        assert int(pairs[3]) == 0
    if case.endswith("a-call-longer-than-one-piece"):
        assert B * T > moe.DENSE_EXPERTS_CALL_TOKENS and int(rounds) >= 1
    if case == "a-long-call-mostly-padding":
        # 4,096 slots of which 1,372 hold a token: the rounds follow the
        # tokens, not the slots
        assert int(rounds) == 0


def test_a_decode_steps_call_is_the_product_over_every_held_expert():
    """64 rows: no ranks, no gather, no loop; the same equations as
    ``dense_expert_compute`` after the router's."""
    cfg = _cfg(**STACKS["window"])
    p = _layer(cfg, jax.random.PRNGKey(7))
    h = jax.random.normal(jax.random.PRNGKey(1), (64, 1, D))
    assert moe.group_rows(cfg, 64) == 0
    text = str(jax.make_jaxpr(lambda h: moe.held_moe_mlp(cfg, h, p, router_input=h))(h))
    assert "while" not in text and "gather" not in text and "cumsum" not in text
    assert moe.held_moe_mlp(cfg, h, p, router_input=h)[3] is None
    fill = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, D))
    text = str(jax.make_jaxpr(lambda h: moe.held_moe_mlp(cfg, h, p, router_input=h))(fill))
    assert "while" in text and "gather" in text


@pytest.mark.parametrize(
    "stack,tokens,rows",
    [
        # a decode step; a chunk of 256; the knee
        ("window", 64, 0), ("hybrid", 256, 0), ("latent", 256, 0),
        ("window", 512, 0), ("latent", 512, 0), ("window", 1023, 0),
        # the cells' fills: [1, 1024] and [4, 256]
        ("window", 1024, 256), ("latent", 1024, 256), ("hybrid", 1024, 256),
        # [4, 1024] and a whole sequence: more rounds, not larger groups
        ("window", 4096, 256), ("latent", 16384, 256), ("hybrid", 1100, 256),
    ],
)
def test_the_form_follows_from_the_calls_shape(stack, tokens, rows):
    assert moe.group_rows(_cfg(**STACKS[stack]), tokens) == rows
    none_held = dataclasses.replace(_cfg(**STACKS[stack]), moe_held_experts=0)
    assert moe.group_rows(none_held, tokens) == 0
    # with a Mamba layer in the stack: never (the fault of PR 41, see
    # ``moe.group_rows``)
    assert moe.group_rows(_cfg(**STACKS[stack], **RECURRENT), tokens) == 0


# a decode step, the hybrid cell's widest fill, a whole sequence, [4, 1024]
@pytest.mark.parametrize("shape", [(64, 1), (4, 256), (1, 1100), (4, 1024)])
def test_a_stack_with_recurrent_state_multiplies_every_held_expert(shape):
    """At any length: no ranks, no gather, no loop of rounds in its
    programs (a long call goes in pieces, a scan), no count of rounds, and
    what one product over every held expert gives."""
    cfg = _cfg(**STACKS["hybrid"], **RECURRENT)
    p = _layer(cfg, jax.random.PRNGKey(7))
    B, T = shape
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, D))
    valid = jnp.arange(T)[None, :] < jnp.asarray([T, T // 3, 0, 7] * 16)[:B, None]
    text = str(jax.make_jaxpr(lambda h: moe.held_moe_mlp(cfg, h, p, valid=valid))(h))
    assert "while" not in text and "gather" not in text and "cumsum" not in text
    with jax.default_matmul_precision("highest"):
        got, _, _, rounds = jax.jit(
            lambda h: moe.held_moe_mlp(cfg, h, p, valid=valid)
        )(h)
        want = _every_held_expert(cfg, h, p, None)
    assert rounds is None
    assert np.abs(np.asarray(got - want)).max() < 1e-5


#: case -> (stack, [B, T], padding, the router's twist): the grouped
#: product's BACKWARD against the gradient of the product over every held
#: expert; "skew" gives one expert every token (four rounds at 1,024)
BACKWARD = {
    "latent-held-elsewhere-padding": ("latent", (4, 256), True, None),
    "window-all-held-relu": ("window", (1, 1024), False, None),
    "hybrid-shared-expert-two-rounds": ("hybrid", (1, 1100), False, None),
    "busiest-expert-needs-extra-rounds": ("window", (1, 1024), False, "skew"),
}


@pytest.mark.parametrize("case", sorted(BACKWARD))
def test_grouped_backward_is_the_gradient_of_every_held_expert(case):
    """``dx``, the three ``dW`` of each held expert, the router's and the
    shared expert's gradients of the grouped form (a layer's slice in
    hand, as the trainer's scan hands it) against ``jax.grad`` of the
    dense form, under a routing that needs extra rounds too: no pair is
    dropped backward either."""
    stack, (B, T), padded, twist = BACKWARD[case]
    cfg = _cfg(**STACKS[stack])
    p = _layer(cfg, jax.random.PRNGKey(7))
    kh, ka, kc = jax.random.split(jax.random.PRNGKey(13), 3)
    h = jax.random.normal(kh, (B, T, D))
    a = jax.random.normal(ka, (B, T, D)) if cfg.moe_router_input == "attn" else None
    if twist == "skew":
        p["router"]["w"] = p["router"]["w"].at[:, 5].set(0.0)
        h = h.at[..., 0].set(1.0)
        a = None if a is None else a.at[..., 0].set(1.0)
        p["router"]["w"] = p["router"]["w"].at[0].set(
            jnp.zeros((cfg.n_experts,)).at[5].set(50.0)
        )
    valid = None
    if padded:
        lens = jnp.asarray([T, T // 3, 0, 7][:B])
        valid = jnp.arange(T)[None, :] < lens[:, None]
    keep = jnp.ones((B, T), bool) if valid is None else valid
    ct = jax.random.normal(kc, (B, T, D)) * keep[..., None]

    def grouped(h, p):
        out, _, _, rounds = moe.held_moe_mlp(
            cfg, h, p, valid=valid, router_input=a
        )
        return jnp.sum(out * ct), rounds

    def dense(h, p):
        return jnp.sum(_every_held_expert(cfg, h, p, a) * ct)

    with jax.default_matmul_precision("highest"):
        (_, rounds), got = jax.jit(
            jax.value_and_grad(grouped, (0, 1), has_aux=True)
        )(h, p)
        want = jax.jit(jax.grad(dense, (0, 1)))(h, p)
    if twist == "skew":
        assert int(rounds) == B * T // moe.GROUP_ROWS - 1 > 0
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        g, w = np.asarray(g), np.asarray(w)
        if g.ndim == 3 and g.shape[:2] == (B, T):
            # padding's dx: the dense form multiplies padding's rows too
            g, w = g[np.asarray(keep)], w[np.asarray(keep)]
        scale = max(np.abs(w).max(), 1e-6)
        assert np.abs(g - w).max() < 2e-5 * scale + 1e-6, (path, np.abs(g - w).max(), scale)
    if "bias" in p["router"]:
        # the choice bias takes part in the choice alone: no gradient
        assert not np.asarray(got[1]["router"]["bias"]).any()


#: case -> (tokens, k, held, of experts, rows a tile, the routing's twist)
TILES = {
    "even": (512, 4, 8, 16, 32, None),
    "one-expert-takes-every-token": (512, 4, 8, 16, 32, "skew"),
    "padding-and-an-idle-expert": (300, 2, 4, 16, 16, "padded"),
}


@pytest.mark.parametrize("case", sorted(TILES))
def test_the_trainers_tiles_hold_each_held_pair_once_and_cost_the_pairs(case):
    """The layout ``grouped_expert_train`` loops over: every held pair of a
    valid token lies in exactly one live row of its expert's tiles, where
    ``_pair_rows`` says; the tiles are the pairs rounded up an expert,
    whatever the busiest expert took."""
    N, K, held, E, cap, twist = TILES[case]
    rng = np.random.default_rng(3)
    local = np.stack([rng.permutation(E)[:K] for _ in range(N)]).astype(np.int32)
    valid = None
    if twist == "skew":
        local[:, 0] = 5
        local[:, 1:] = np.where(local[:, 1:] == 5, 15, local[:, 1:])
    if twist == "padded":
        valid = np.arange(N) < 200
        local = np.where(local == 2, 9, local)  # held expert 2 idles
    is_held, cum, rank, _, _ = moe._pair_ranks(
        jnp.asarray(local), None if valid is None else jnp.asarray(valid), held, cap
    )
    count, starts, ends = moe._tile_layout(cum, cap)
    count = np.asarray(count)
    pairs = int(np.asarray(is_held).sum())
    assert count.sum() == pairs
    tiles = int(ends[-1])
    assert tiles == sum(-(-c // cap) for c in count)
    assert pairs <= tiles * cap < pairs + held * cap
    assert tiles * cap <= moe._tile_rows(N, K, held, cap)
    row = np.asarray(moe._pair_rows(jnp.asarray(local), is_held, rank, starts, cap))
    seen = {}
    for c in range(-(-tiles // moe.TRAIN_TILES)):
        e, live, tok = (
            np.asarray(a)
            for a in moe._pass_tiles(c, cap, jnp.asarray(count), starts, ends, cum.T)
        )
        for g in range(moe.TRAIN_TILES):
            for r in np.nonzero(live[g])[0]:
                at = (c * moe.TRAIN_TILES + g) * cap + r
                assert at not in seen
                seen[at] = (int(tok[g, r]), int(e[g]))
    held_pairs = {
        int(row[n, k]): (n, int(local[n, k]))
        for n, k in zip(*np.nonzero(np.asarray(is_held)))
    }
    assert seen == held_pairs
    if twist == "skew":
        assert count[5] == N and tiles * cap < 2 * pairs
