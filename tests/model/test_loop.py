"""A LOOPED dense stack (``ouro``: the layers run ``loop_steps`` times with
the same weights, a cache of its own for every (pass, layer), sandwich
norms, the final norm after every pass) against its plain reference
(``benchmark/lib/reference_ouro.py``), at small sizes on seeded weights:
LOGITS, not tokens, of the train forward, of chunked paged fills and paged
decode chunks, and of the dense prefill and decode chunk."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import areal_tpu.models.hf  # noqa: F401 - registers the families
from areal_tpu.engine import kv_pages
from areal_tpu.models import paged
from areal_tpu.models import transformer as tf
from areal_tpu.models.config import tiny_config
from areal_tpu.models.hf.registry import get_hf_family
from areal_tpu.system import flops_counter
from benchmark.lib import reference_ouro as ref

TOL = 1e-5

#: 3 layers x 3 passes, d 64, 4 query = 4 KV heads of 16
HF = dict(
    architectures=["OuroForCausalLM"], model_type="ouro", hidden_size=64,
    intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, vocab_size=256,
    max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None, sliding_window=None, use_sliding_window=False,
    tie_word_embeddings=False, hidden_act="silu", total_ut_steps=3,
    early_exit_threshold=1,
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        get_hf_family("ouro").config_from_hf(HF), dtype="float32"
    )
    return cfg, tf.init_params_in_dtype(cfg, jax.random.PRNGKey(7))


def _tokens(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(3, 256, n)]


def _ref_logits(params, seq, **kw):
    return np.asarray(ref.forward_logits(HF, params, seq, **kw)[0])


def test_the_adapters_config(model):
    cfg, params = model
    assert (cfg.n_layers, cfg.loop_steps, cfg.n_attn_layers) == (3, 3, 9)
    assert cfg.sandwich_norm and cfg.loop_exit_gate and not cfg.tied_embedding
    assert cfg.sliding_window is None and cfg.rotary_base == 1000000
    assert set(params["layers"]) == {
        "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "attn",
        "mlp",
    }
    # every pool shape and page byte count reads the CACHE layers
    assert paged.pool_shapes(cfg, 5, 8)[0] == (9, 5, 4, 8, 16)
    assert tf.KVCache.zeros(cfg, 2, 8).k.shape[0] == 9
    assert paged.kv_pool_layout_bytes(cfg, 1, 1) == (9 * 2 * 4 * 16 * 4, 0)
    back = get_hf_family("ouro").config_to_hf(cfg)
    for key in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "vocab_size", "rms_norm_eps", "rope_theta", "total_ut_steps",
        "early_exit_threshold", "tie_word_embeddings", "model_type",
        "architectures", "use_sliding_window", "sliding_window",
    ):
        assert back[key] == HF[key], key


def test_the_adapters_parameter_names_both_ways(model):
    cfg, params = model
    fam = get_hf_family("ouro")
    state = fam.params_to_hf(params, cfg)
    assert state["model.early_exit_gate.weight"].shape == (1, 64)
    assert state["model.early_exit_gate.bias"].shape == (1,)
    for i in range(3):
        for name in (
            "input_layernorm", "input_layernorm_2",
            "post_attention_layernorm", "post_attention_layernorm_2",
        ):
            assert state[f"model.layers.{i}.{name}.weight"].shape == (64,)
    assert state["model.layers.2.self_attn.q_proj.weight"].shape == (64, 64)
    assert not any("bias" in k for k in state if "early_exit" not in k)
    again = fam.params_from_hf(state, cfg)
    flat, tree = jax.tree.flatten(params)
    flat2, tree2 = jax.tree.flatten(again)
    assert tree == tree2
    for a, b in zip(flat, flat2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_forward_is_the_reference(model):
    cfg, params = model
    seq = _tokens(1, 29)
    T = len(seq)
    with jax.default_matmul_precision("highest"):
        got = tf.forward(
            params, cfg, jnp.asarray([seq]), jnp.arange(T)[None],
            jnp.ones((1, T), jnp.int32),
        )
    assert np.abs(np.asarray(got[0]) - _ref_logits(params, seq)).max() < TOL


def test_the_exit_rule_picks_the_last_pass_at_threshold_1(model):
    _, params = model
    _, lam = ref.forward_logits(HF, params, _tokens(2, 17))
    lam = np.asarray(lam)
    assert lam.shape == (3, 17) and (0 < lam).all() and (lam < 1).all()
    assert (ref.exit_pass(lam, 1.0) == 3).all()
    # ... and earlier below it: what the program refuses to serve
    assert (ref.exit_pass(lam, 0.5) < 3).any()
    assert (ref.exit_pass(np.full((3, 2), 0.999), 0.9) == 1).all()


def _forced(seqs, sink):
    """A sample function that hands each row the next token of ITS
    sequence and leaves every step's logits in ``sink``."""
    seqs = jnp.asarray(seqs)

    def sample(logits, _rng, positions):
        jax.debug.callback(
            lambda p, l: sink.append((np.asarray(p), np.asarray(l))),
            positions, logits,
        )
        tok = seqs[jnp.arange(seqs.shape[0]), positions]
        lp = jax.nn.log_softmax(logits)
        return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]

    return sample


def _never_stop(tok):
    return jnp.zeros_like(tok, bool)


def _paged_logits(cfg, params, seq, P, piece, use_kernel, W=4):
    """``seq[:P]`` through ``paged_fill_chunk`` in pieces of ``piece`` (row
    1 of 2, scattered pages of 8), then ``seq[P:]`` teacher-forced through
    ``paged_decode_chunk`` in chunks of ``W``.  Returns the logits that
    predict positions P .. len(seq) - 1."""
    BS, MB = 8, 8
    k_pool, v_pool = paged.pool_zeros(cfg, 16, BS)
    assert k_pool.shape[0] == cfg.n_layers * cfg.loop_steps
    tables = np.zeros((2, MB), np.int32)
    tables[1] = [3, 5, 7, 9, 11, 13, 1, 2]
    out = []
    with jax.default_matmul_precision("highest"):
        pos = 0
        while pos < P:
            take = min(piece, P - pos)
            toks = np.zeros((2, 16), np.int32)
            toks[1, :take] = seq[pos : pos + take]
            logits, k_pool, v_pool = paged.paged_fill_chunk(
                params, k_pool, v_pool, cfg, jnp.asarray(toks),
                jnp.asarray([0, pos], jnp.int32),
                jnp.asarray([0, take], jnp.int32), jnp.asarray(tables),
                use_kernel=use_kernel,
            )
            pos += take
        out.append(np.asarray(logits[1]))
        sink = []
        sample = _forced([[0] * len(seq), seq], sink)
        lens = jnp.asarray([0, P], jnp.int32)
        cur = jnp.asarray([0, seq[P]], jnp.int32)
        act = jnp.asarray([False, True])
        bud = jnp.asarray([0, len(seq) - P - 1], jnp.int32)
        while bool(act[1]):
            (k_pool, v_pool, lens, _t, _l, _e, cur, act, bud, _) = (
                paged.paged_decode_chunk(
                    params, k_pool, v_pool, cfg, jnp.asarray(tables), lens,
                    cur, act, bud, jax.random.PRNGKey(0), W, sample,
                    _never_stop, use_kernel=use_kernel, max_len=BS * MB,
                )
            )
        jax.effects_barrier()
    by_pos = {int(p[1]): l[1] for p, l in sink if p[1] <= len(seq) - 1}
    # the step that fed position p - 1 predicts position p
    out += [by_pos[p] for p in range(P + 1, len(seq))]
    return np.stack(out)


# a prompt of 29 crosses the page of 8 three times; fill pieces of 5 and 13
# line up with no page; 14 more tokens go through decode chunks of 4
@pytest.mark.parametrize("use_kernel, piece", [(False, 5), (True, 13)])
def test_paged_fill_in_chunks_then_decode_chunks_are_the_reference(
    model, use_kernel, piece
):
    cfg, params = model
    seq, P = _tokens(3, 44), 29
    got = _paged_logits(cfg, params, seq, P, piece, use_kernel)
    want = _ref_logits(params, seq)[P - 1 : -1]
    assert got.shape == want.shape == (15, 256)
    assert np.abs(got - want).max() < TOL


def test_passes_do_not_share_a_cache(model):
    """The reference with ONE cache a layer (every pass overwrites it) is
    far from the program, which is at the true reference: this fails the
    day the passes of a served row share their pages."""
    cfg, params = model
    seq, P = _tokens(4, 30), 13
    got = _paged_logits(cfg, params, seq, P, 13, use_kernel=False)
    want = _ref_logits(params, seq)[P - 1 : -1]
    shared = _ref_logits(params, seq, wrong="shared_cache")[P - 1 : -1]
    assert np.abs(got - want).max() < TOL
    assert np.abs(got - shared).max() > 1000 * TOL
    # the first position has no earlier token: one cache or three, the same
    first = _ref_logits(params, seq[:1], wrong="shared_cache")
    assert np.abs(first - _ref_logits(params, seq[:1])).max() < TOL


def test_dense_prefill_then_decode_chunk_are_the_reference(model):
    cfg, params = model
    seq, P = _tokens(5, 40), 21
    cache = tf.KVCache.zeros(cfg, 2, 64)
    toks = np.zeros((2, 24), np.int32)
    toks[1, :P] = seq[:P]
    pos = np.tile(np.arange(24, dtype=np.int32)[None], (2, 1))
    seg = np.zeros((2, 24), np.int32)
    seg[1, :P] = 1
    sink = []
    with jax.default_matmul_precision("highest"):
        logits, cache = tf.prefill(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(seg),
            cache, last_pos=jnp.asarray([0, P - 1]),
        )
        out = [np.asarray(logits[1, 0])]
        # one decode_step, then a chunk of the rest
        step_logits, cache = tf.decode_step(
            params, cfg, jnp.asarray([0, seq[P]], jnp.int32), cache,
            active=jnp.asarray([False, True]),
        )
        out.append(np.asarray(step_logits[1]))
        n = len(seq) - P - 2
        tf.decode_chunk(
            params, cfg, cache, jnp.asarray([0, seq[P + 1]], jnp.int32),
            jnp.asarray([False, True]), jnp.asarray([0, n], jnp.int32),
            jax.random.PRNGKey(0), n,
            _forced([[0] * len(seq), seq], sink), _never_stop,
        )
        jax.effects_barrier()
    by_pos = {int(p[1]): l[1] for p, l in sink}
    out += [by_pos[p] for p in range(P + 2, len(seq))]
    want = _ref_logits(params, seq)[P - 1 : -1]
    assert np.abs(np.stack(out) - want).max() < TOL


def test_the_gradient_is_the_references(model):
    """``jax.grad`` of ``logprobs_of_labels`` against the reference's: a
    tied weight's gradient is the sum over the passes."""
    cfg, params = model
    seq = _tokens(6, 19)
    T = len(seq)

    def loss(p):
        lps = tf.logprobs_of_labels(
            p, cfg, jnp.asarray([seq]), jnp.arange(T)[None],
            jnp.ones((1, T), jnp.int32),
        )
        return jnp.mean(lps)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss)(params)
    want = ref.mean_logp_grad(HF, params, seq)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "exit_gate" in name:  # moves no logit at threshold 1
            assert float(jnp.abs(g).max()) == 0.0
            continue
        w = want
        for k in path:
            w = w[k.key]
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        assert float(jnp.abs(g - w).max()) < 2e-5 * max(scale, 1.0), name
    # one pass's gradient is not the three passes': the weights are tied
    once = dataclasses.replace(cfg, loop_steps=1)
    with jax.default_matmul_precision("highest"):
        g1 = jax.grad(
            lambda p: jnp.mean(
                tf.logprobs_of_labels(
                    p, once, jnp.asarray([seq]), jnp.arange(T)[None],
                    jnp.ones((1, T), jnp.int32),
                )
            )
        )(params)
    q, q1 = got["layers"]["attn"]["q"]["w"], g1["layers"]["attn"]["q"]["w"]
    assert float(jnp.abs(q - q1).max()) > 1e-3 * float(jnp.abs(q).max())


# -- at loop_steps 1 nothing is added ---------------------------------------


def _scans(jaxpr, inside=()):
    """``(scopes of the scans it is nested in, its own scope)`` of every
    ``scan`` under ``jaxpr``, and every equation's scope."""
    scans, scopes = [], set()
    for eqn in jaxpr.eqns:
        scope = str(eqn.source_info.name_stack)
        scopes.add(scope)
        if eqn.primitive.name == "scan":
            scans.append((inside, scope))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            more, seen = _scans(
                sub, inside + ((scope,) if eqn.primitive.name == "scan" else ())
            )
            scans += more
            scopes |= seen
    return scans, scopes


def _programs(cfg, params):
    """The jaxprs of the fill, the decode chunk and the train forward."""
    BS, MB = 8, 4
    pools = paged.pool_zeros(cfg, 8, BS)
    tables = jnp.zeros((2, MB), jnp.int32)
    z = jnp.zeros((2,), jnp.int32)
    fill = jax.make_jaxpr(
        lambda p, k, v: paged.paged_fill_chunk(
            p, k, v, cfg, jnp.zeros((2, 16), jnp.int32), z, z + 3, tables,
            use_kernel=False,
        )
    )(params, *pools)
    greedy = lambda logits, _rng: (
        jnp.argmax(logits, -1).astype(jnp.int32), jnp.max(logits, -1)
    )
    decode = jax.make_jaxpr(
        lambda p, k, v: paged.paged_decode_chunk(
            p, k, v, cfg, tables, z + 5, z, z == 0, z + 4,
            jax.random.PRNGKey(0), 4, greedy, _never_stop, use_kernel=False,
            max_len=BS * MB,
        )
    )(params, *pools)
    train = jax.make_jaxpr(
        lambda p: tf.logprobs_of_labels(
            p, cfg, jnp.zeros((1, 16), jnp.int32), jnp.arange(16)[None],
            jnp.ones((1, 16), jnp.int32),
        )
    )(params)
    return {"fill": fill, "decode": decode, "train": train}


def test_without_a_loop_each_program_holds_its_one_layer_scan_and_no_scope():
    """The tiny qwen2 config's fill, decode chunk and train forward: ONE
    scan in the scope ``areal.layers`` each, as before this stack came, no
    scan around it but the decode chunk's step loop, no ``areal.loop``
    scope and no sandwich norm's parameters."""
    cfg = tiny_config(use_attention_bias=True)  # qwen2
    assert cfg.loop_steps == 1 and cfg.n_attn_layers == cfg.n_layers
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    assert "attn_post_norm" not in params["layers"] and "exit_gate" not in params
    for name, jaxpr in _programs(cfg, params).items():
        scans, scopes = _scans(jaxpr.jaxpr)
        layer_scans = [s for s in scans if s[1].endswith("areal.layers")]
        assert len(layer_scans) == 1, (name, scans)
        # in no scan but the decode chunk's step loop (a fori_loop)
        assert layer_scans[0][0] == (("",) if name == "decode" else ()), (
            name, layer_scans,
        )
        assert not any("areal.loop" in s for s in scopes), name


def test_a_loop_is_one_outer_scan_around_one_layer_scan(model):
    cfg, params = model
    for name, jaxpr in _programs(cfg, params).items():
        scans, scopes = _scans(jaxpr.jaxpr)
        layer_scans = [s for s in scans if s[1].endswith("areal.layers")]
        # the layer body is traced once, inside the scan over the passes
        assert len(layer_scans) == 1, (name, scans)
        around = layer_scans[0][0]
        assert len(around) == (2 if name == "decode" else 1), (name, around)
        assert around[-1].endswith("areal.loop"), (name, around)
        assert any(s.endswith("areal.loop.norm") for s in scopes), name


# -- what a looped stack refuses, by name, and what it does not ---------------


def test_any_other_exit_threshold_is_refused_by_its_key():
    with pytest.raises(NotImplementedError, match="early_exit_threshold 0.9"):
        get_hf_family("ouro").config_from_hf(dict(HF, early_exit_threshold=0.9))


def test_a_pipeline_mesh_is_refused(model):
    cfg, params = model
    x = jnp.zeros((2, 8, 64))
    with pytest.raises(NotImplementedError, match="loop_steps 3 on a pipeline"):
        tf._run_layers_pipelined(params, cfg, x, None, None, None, None, None)


def test_a_looped_stack_holds_no_cache_kind_that_refuses(model):
    cfg, _ = model
    held = kv_pages.kinds_held(cfg)
    assert held == {}
    # every feature stands: a page id names its slice of ALL layers
    for feature in kv_pages.REFUSED:
        kv_pages.refuse(feature, held)


def test_a_stack_stated_by_kind_does_not_loop():
    with pytest.raises(AssertionError, match="loop_steps > 1"):
        tiny_config(layer_types=("attention", "attention"), loop_steps=2)


def test_a_token_costs_every_pass_of_the_layers_and_one_head(model):
    cfg, _ = model
    once = dataclasses.replace(cfg, loop_steps=1)
    head = 2 * 64 * 256 * 10
    looped = flops_counter.forward_flops(cfg, [10])
    assert looped - head == 3 * (flops_counter.forward_flops(once, [10]) - head)
    assert flops_counter.layer_passes(cfg) == 9
    gen = flops_counter.generate_flops(cfg, [8], [4])
    gen1 = flops_counter.generate_flops(once, [8], [4])
    assert gen - 4 * 2 * 64 * 256 == 3 * (gen1 - 4 * 2 * 64 * 256)
