"""Device regions (``observability.tracing.region``): every step program's
products lie in a region, the train step shows the three passes, a region
adds no operation, and the lint holds the names to the table.  CPU, toy
sizes: what is checked is the scope paths of the COMPILED program's
operations (``op_name``), which is what the profiler writes out as
``tf_op``."""

import contextlib
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, paged
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.models.hf.registry import family_from_architecture
from areal_tpu.observability import tracing
from areal_tpu.observability.table import TRACE_TABLE
from benchmark.lib.region_reduce import UNNAMED
from benchmark.lib.region_reduce import region_of as _region_of

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
REGIONS = {s.name for s in TRACE_TABLE if s.kind == "region"}
#: an operation's scope path in the compiled text, and the operations whose
#: every instance must carry a region: the matrix products and convolutions
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PRODUCT = re.compile(r"/(dot_general|conv_general_dilated|ragged_dot)$")


def _paths(lowered):
    """Every scope path of the compiled program (``jit(f)/.../primitive``)."""
    return sorted(set(_OP_NAME.findall(lowered.compile().as_text())))


def _assert_products_in_regions(paths, want):
    products = [p for p in paths if _PRODUCT.search(p)]
    assert products, "the program has no product at all?"
    bare = [p for p in products if _region_of(p) in UNNAMED]
    assert not bare, bare
    seen = {_region_of(p) for p in paths} - set(UNNAMED)
    assert seen <= REGIONS, seen - REGIONS
    assert want <= seen, want - seen


def _greedy(logits, _rng, _positions, _seeds):
    lp = jax.nn.log_softmax(logits)
    tok = jnp.argmax(lp, -1)
    return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


# -- the dense stack's two programs -----------------------------------------

B, BS, MB, NB = 2, 8, 4, 8


def _dense():
    cfg = tiny_config(vocab_size=64)
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense():
    return _dense()


def _lower(call):
    jitted, args, kwargs = call
    return jitted.lower(*args, **kwargs)


def _paged_decode(cfg, params):
    k_pool, v_pool = paged.pool_zeros(cfg, NB, BS)
    return paged.paged_decode_chunk, (
        params, k_pool, v_pool, cfg, jnp.zeros((B, MB), jnp.int32),
        jnp.full((B,), 3, jnp.int32), jnp.ones((B,), jnp.int32),
        jnp.ones((B,), bool), jnp.full((B,), 4, jnp.int32),
        jax.random.PRNGKey(0), 2, _greedy, _never_stop,
    ), dict(
        use_kernel=False, max_len=32, row_seeds=jnp.zeros((B,), jnp.int32)
    )


def _paged_fill(cfg, params):
    k_pool, v_pool = paged.pool_zeros(cfg, NB, BS)
    return paged.paged_fill_chunk, (
        params, k_pool, v_pool, cfg, jnp.ones((B, 8), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.full((B,), 5, jnp.int32),
        jnp.zeros((B, MB), jnp.int32),
    ), dict(use_kernel=False)


def test_paged_decode_chunk_names_its_regions(dense):
    _assert_products_in_regions(
        _paths(_lower(_paged_decode(*dense))),
        {"areal.embed", "areal.attn", "areal.kv_write", "areal.mlp",
         "areal.head", "areal.sample"},
    )


def test_paged_fill_chunk_names_its_regions(dense):
    _assert_products_in_regions(
        _paths(_lower(_paged_fill(*dense))),
        {"areal.embed", "areal.attn", "areal.kv_write", "areal.mlp",
         "areal.head"},
    )


# -- the stack stated by kind: Mamba / attention / experts, and latent ------

GRANITE = dict(
    architectures=["GraniteMoeHybridForCausalLM"], hidden_size=32,
    intermediate_size=16, shared_intermediate_size=24, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
    num_experts_per_tok=3, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
    mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8,
    attention_multiplier=0.2, embedding_multiplier=3.0,
    residual_multiplier=0.5, logits_scaling=2.0, rms_norm_eps=1e-5,
    tie_word_embeddings=True, vocab_size=64,
)
DEEPSEEK = dict(
    architectures=["DeepseekV3ForCausalLM"], vocab_size=64,
    max_position_embeddings=256, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=3, num_nextn_predict_layers=1,
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
SMALLTHINKER = dict(
    architectures=["SmallThinkerForCausalLM"], hidden_size=32,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, moe_ffn_hidden_size=16, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=12, tie_word_embeddings=False, vocab_size=64,
)
STACKS = {
    "hybrid": (GRANITE, {"areal.ssm", "areal.attn", "areal.moe.shared"}),
    "latent": (DEEPSEEK, {"areal.attn", "areal.mlp", "areal.moe.shared"}),
    # the window layers' half has a region of its own, the global layer's
    # keeps ``areal.attn``; no shared expert
    "window": (SMALLTHINKER, {"areal.attn", "areal.attn.window"}),
}
SLOTS = 2


def _stack(name):
    hf, _ = STACKS[name]
    cfg = family_from_architecture(hf["architectures"][0]).config_from_hf(hf)
    cfg = dataclasses.replace(cfg, dtype="float32")
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _window_pools(cfg, rows):
    """The window layers' pools and table, where the stack has such."""
    if not cfg.n_window_layers:
        return {}
    return dict(
        win_pools=paged.pool_zeros(cfg, NB, BS, layers=cfg.n_window_layers),
        win_tables=jnp.zeros((rows, MB), jnp.int32),
    )


def _hybrid_decode(cfg, params):
    k_pool, v_pool = paged.pool_zeros(cfg, NB, BS)
    ssm, conv = hybrid.state_zeros(cfg, SLOTS)
    return hybrid.hybrid_decode_chunk, (
        params, k_pool, v_pool, ssm, conv, cfg,
        jnp.zeros((SLOTS, MB), jnp.int32), jnp.full((SLOTS,), 3, jnp.int32),
        jnp.ones((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool),
        jnp.full((SLOTS,), 4, jnp.int32), jax.random.PRNGKey(0), 2, _greedy,
        _never_stop,
    ), dict(use_kernel=False, max_len=32, **_window_pools(cfg, SLOTS))


def _hybrid_fill(cfg, params):
    k_pool, v_pool = paged.pool_zeros(cfg, NB, BS)
    ssm, conv = hybrid.state_zeros(cfg, SLOTS)
    return hybrid.hybrid_fill_chunk, (
        params, k_pool, v_pool, ssm, conv, cfg, jnp.ones((2, 8), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32),
        jnp.zeros((2, MB), jnp.int32), jnp.arange(2, dtype=jnp.int32),
    ), dict(use_kernel=False, **_window_pools(cfg, 2))


@pytest.mark.parametrize("name", sorted(STACKS))
@pytest.mark.parametrize(
    "program, also",
    [(_hybrid_decode, {"areal.sample"}), (_hybrid_fill, set())],
    ids=["decode", "fill"],
)
def test_hybrid_programs_name_their_regions(name, program, also):
    cfg, params = _stack(name)
    want = STACKS[name][1] | also | {
        "areal.embed", "areal.kv_write", "areal.moe.route",
        "areal.moe.experts", "areal.head",
    }
    _assert_products_in_regions(_paths(_lower(program(cfg, params))), want)


PHI4FLASH = dict(
    architectures=["Phi4FlashForCausalLM"], hidden_size=32,
    num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
    intermediate_size=48, vocab_size=64, sliding_window=12, mb_per_layer=2,
    layer_norm_eps=1e-5, tie_word_embeddings=True,
)


@pytest.mark.parametrize(
    "program, also",
    [(_hybrid_decode, {"areal.sample"}), (_hybrid_fill, set())],
    ids=["decode", "fill"],
)
def test_a_decoder_hybrid_decoder_stack_splits_its_five_mixer_kinds(program, also):
    """``[mamba1, window] x 2, mamba1, attention, gmu, cross``: the Mamba-1
    mixers in ``areal.ssm``, the window layers, the one full-attention
    layer and the cross layer each in a region of their own, the gated
    memory unit in ``areal.gmu``; no expert layer, so no ``areal.moe.*``."""
    cfg = family_from_architecture("Phi4FlashForCausalLM").config_from_hf(PHI4FLASH)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    paths = _paths(_lower(program(cfg, params)))
    _assert_products_in_regions(
        paths,
        also | {
            "areal.embed", "areal.ssm", "areal.attn", "areal.attn.window",
            "areal.attn.cross", "areal.gmu", "areal.mlp", "areal.kv_write",
            "areal.head",
        },
    )
    assert not any("areal.moe" in p for p in paths)


# -- the PPO train step ---------------------------------------------------------


def _train_step(engines=None, vocab_size=64):
    """The PPO actor's fused train step at a toy size, rematerialised."""
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.train_engine import TrainEngine
    from areal_tpu.interfaces.ppo_interface import PPOActorInterface

    cfg = tiny_config(vocab_size=vocab_size, remat=True)
    mesh = MeshSpec(data=1, fsdp=1, model=1).make_mesh(jax.devices()[:1])
    engine = TrainEngine(
        cfg, mesh, tfm.init_params(cfg, jax.random.PRNGKey(0)),
        optimizer_cfg=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        total_train_steps=10,
    )
    T = 16
    seg = (np.arange(T) < 12).astype(np.int32)[None, None]
    batch = {
        "tokens": np.ones((1, 1, T), np.int32),
        "positions": np.arange(T, dtype=np.int32)[None, None],
        "seg_ids": seg,
        "ppo_loss_mask": seg.astype(np.float32),
        "packed_logprobs": np.zeros((1, 1, T), np.float32),
        "advantages": np.ones((1, 1, T), np.float32),
    }
    step = engine._get_train_step(PPOActorInterface()._loss_fn, 1)
    if engines is not None:
        engines.append(engine)
    return step, (engine.params, engine.opt_state, batch), {}


def test_train_step_shows_the_three_passes_and_its_own_regions():
    engines = []
    paths = _paths(_lower(_train_step(engines)))
    _assert_products_in_regions(
        paths,
        {"areal.embed", "areal.attn", "areal.mlp", "areal.head",
         "areal.loss", "areal.optimizer"},
    )
    for name in ("areal.attn", "areal.mlp"):
        of = [p for p in paths if _region_of(p) == name]
        backward = [p for p in of if "transpose(" in p]
        remat = [p for p in of if "rematted_computation" in p]
        forward = [
            p for p in of
            if "transpose(" not in p and "rematted_computation" not in p
        ]
        assert forward and backward and remat, (name, of)
    # the trainer's head product is the loss's, not the head's
    head_products = [
        p for p in paths if _PRODUCT.search(p) and _region_of(p) == "areal.head"
    ]
    assert not head_products, head_products
    # a token-sum loss takes each chunk's gradient inside its forward scan
    # (the chunk's own transpose(jvp())): three head products a token, the
    # count the span areal.train.batch carries, and no recomputed pass
    of_loss = [p for p in paths if _region_of(p) == "areal.loss"]
    assert any(_PRODUCT.search(p) and "transpose(" in p for p in of_loss)
    assert not [p for p in of_loss if "rematted_computation" in p]
    assert list(engines[0]._loss_head_products.values()) == [3]
    assert not any(
        "transpose(" in p or "jvp(" in p
        for p in paths if _region_of(p) == "areal.optimizer"
    )


# -- a region adds no operation -----------------------------------------------


def _equations(jaxpr):
    """Every equation's primitive and output types, sub-programs inlined
    in order: what the program DOES."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, tuple(str(v.aval) for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_equations(sub))
    return out


def _traced(call):
    """What a jitted program's own function traces to, from scratch (its
    arguments closed over: no cache of the jitted program is asked)."""
    jitted, args, kwargs = call
    return jax.make_jaxpr(lambda: jitted.__wrapped__(*args, **kwargs))().jaxpr


PROGRAMS = {
    "paged_decode_chunk": lambda: _paged_decode(*_dense()),
    "paged_fill_chunk": lambda: _paged_fill(*_dense()),
    "hybrid_decode_chunk": lambda: _hybrid_decode(*_stack("hybrid")),
    "latent_fill_chunk": lambda: _hybrid_fill(*_stack("latent")),
    "train_step": _train_step,
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_regions_add_no_operation(name, monkeypatch):
    """A program is equation for equation the same with every region a
    null context (a decorated function enters ``jax.named_scope`` anew at
    each call, so the patch reaches it too)."""
    call = PROGRAMS[name]()
    with_regions = _equations(_traced(call))
    entered = []
    monkeypatch.setattr(
        jax, "named_scope",
        lambda scope: entered.append(scope) or contextlib.nullcontext(),
    )
    without = _equations(_traced(call))
    monkeypatch.undo()
    assert entered and set(entered) <= REGIONS
    assert with_regions == without
    assert len(with_regions) > 50


def test_a_region_is_a_named_scope_and_nothing_else():
    def f(x):
        with tracing.region("areal.mlp"):
            return jnp.tanh(x) * 2

    def g(x):
        return jnp.tanh(x) * 2

    one = jnp.ones(3)
    assert str(jax.make_jaxpr(f)(one)) == str(jax.make_jaxpr(g)(one))
    text = jax.jit(f).lower(one).as_text(debug_info=True)
    assert "areal.mlp/tanh" in text
    assert "areal." not in jax.jit(g).lower(one).as_text(debug_info=True)


# -- the lint ------------------------------------------------------------------------


def _lint():
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import check_metric_names as lint
    finally:
        sys.path.pop(0)
    return lint


def test_lint_refuses_an_undeclared_region_and_a_computed_name():
    lint = _lint()
    sites = lint.collect_region_names(
        sources={
            "areal_tpu/x.py": (
                "from areal_tpu.observability.tracing import region\n"
                "@region('areal.attn')\n"
                "def f(): pass\n"
                "with region('areal.made_up'): pass\n"
                "with tracing.region(name): pass\n"
            )
        }
    )
    assert set(sites) == {"areal.attn", "areal.made_up", "<non-literal>"}
    problems = lint.phase_vocabulary_problems(sites, TRACE_TABLE, kind="region")
    assert any("areal.made_up" in p and "missing" in p for p in problems)
    assert any("non-literal region name" in p for p in problems)
    # declared and used here: no complaint; declared and unused: dead
    # (by the whole name: ``areal.attn.window`` is another entry)
    assert not any("areal.attn " in p for p in problems)
    assert any("areal.attn.window" in p and "never recorded" in p for p in problems)
    assert any("areal.mlp" in p and "never recorded" in p for p in problems)


def test_every_declared_region_is_used_and_documented():
    lint = _lint()
    used = set(lint.collect_region_names()) - {"<non-literal>"}
    assert used == REGIONS
    assert REGIONS <= lint.collect_documented_trace_names()
    assert all(name.startswith("areal.") for name in REGIONS)
