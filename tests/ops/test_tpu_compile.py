"""The main path's Pallas calls, compiled by the installed TPU compiler
for a DESCRIBED v5e:2x2 (nothing attached, nothing runs) at Qwen2.5-1.5B
and Qwen2.5-7B head layouts and the engine's real page/chunk shapes.

Interpret-mode tests (the rest of tests/ops/) cannot see what Mosaic
refuses: VMEM exhaustion, misaligned tiles, a kernel that cannot be
partitioned.  These compiles can, at ~1-9 s each and no chip time.  A
compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` arguments: only the xdist
worker that is handed this file may load the TPU library.  Keep every
such test in THIS file for the same reason.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.models import hybrid, moe, paged, transformer
from areal_tpu.models.config import TransformerConfig
from areal_tpu.ops import flash_attention as fa
from areal_tpu.ops import paged_attention as pa

#: (n_q_heads, n_kv_heads) at head_dim 128
HEADS = {
    "qwen2.5-1.5b": (12, 2),
    "qwen2.5-7b": (28, 4),
    "granite-4.0-h-small": (32, 8),
}
HD = 128
#: the engine's serving shapes: page_size 1024, a 4-block row (4k
#: context), a layer-stacked pool
PAGE, MB, LAYERS, NB = 1024, 4, 4, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp2_mesh(topo):
    return Mesh(np.array(topo.devices[:2]), ("model",))


def _paged_args(model, B, Q, quantized, place):
    """Shapes of one paged-attention call; ``place(shape, dtype, spec)``
    attaches the sharding (``spec`` names the kv-head axis position)."""
    Hq, Hkv = HEADS[model]
    kv_dt = jnp.int8 if quantized else jnp.bfloat16
    heads4 = P(None, None, "model", None)
    args = [
        place((B, Q, Hq, HD), jnp.bfloat16, heads4),
        place((LAYERS, NB, Hkv, PAGE, HD), kv_dt, P(None, None, "model")),
        place((LAYERS, NB, Hkv, PAGE, HD), kv_dt, P(None, None, "model")),
        place((B, MB), jnp.int32, P()),
        place((B,), jnp.int32, P()),
        place((1,), jnp.int32, P()),
    ]
    if quantized:
        args += [
            place((LAYERS, NB, Hkv, PAGE), jnp.float32, heads4)
        ] * 2
    return args


def _call_kernel(q, k, v, tables, lengths, layer, *scales):
    ks, vs = scales if scales else (None, None)
    return pa.paged_flash_attention(
        q, k, v, tables, lengths, layer=layer, k_scale=ks, v_scale=vs
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# (16, 1) is the decode step at max_batch 16, (64, 1) the benchmark's;
# (16, 256) a padded fill batch as _run_fill_batch sends it.  Before the
# VMEM plan the compiler refused 7B heads at EVERY shape (decode
# included) and 1.5B heads at (16, 256): "RESOURCE_EXHAUSTED ... memory
# space vmem".  q is bf16, so the bf16 pools compile the bf16-operand
# dots (the stacked p v dot, 3 x 6 and 3 x 7 rows at Q 1) and the int8
# pools the float32 ones.
@pytest.mark.parametrize(
    "model,B,Q,quantized",
    [
        ("qwen2.5-1.5b", 16, 1, False),
        ("qwen2.5-1.5b", 16, 1, True),
        ("qwen2.5-7b", 16, 1, False),
        ("qwen2.5-7b", 16, 1, True),
        ("qwen2.5-1.5b", 16, 256, False),
        ("qwen2.5-1.5b", 64, 1, False),
        ("qwen2.5-1.5b", 16, 256, True),
        ("qwen2.5-7b", 16, 256, False),
        # the hybrid cell's one attention layer: 8 kv heads of 4 queries,
        # a page of 2 MiB, a decode step of 64 rows and a fill of 4 x 256
        ("granite-4.0-h-small", 64, 1, False),
        ("granite-4.0-h-small", 4, 256, False),
    ],
)
def test_paged_kernel_compiles(one_chip, model, B, Q, quantized):
    """With the tile the rule picks for a page of 1,024 (a copy
    descriptor for each number of filled tiles of each stream), under the
    stated VMEM limit."""

    def place(shape, dtype, _spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    Hq, Hkv = HEADS[model]
    tile = pa.page_tile(
        (Hkv, PAGE, HD), jnp.int8 if quantized else jnp.bfloat16
    )
    # the three cells' heads: 256 KiB of one pool a tile
    assert tile == {(2, False): 512, (4, False): 256, (8, False): 256,
                    (2, True): 1024, (4, True): 512}[Hkv, quantized]
    args = _paged_args(model, B, Q, quantized, place)
    compiled = jax.jit(_call_kernel).lower(*args).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("B,Q", [(64, 1), (1, 1024)])
def test_latent_paged_kernel_compiles(one_chip, B, Q):
    """The latent cell's kernel alone: one pool of 640-column pages of 512
    tokens, 64 query heads on the one stream, rows of ten pages (three
    grid steps, so a stream's two buffers take turns within a row)."""

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((5, 640, 1, 512, 640), jnp.bfloat16)
    assert pa.page_tile(pool.shape, pool.dtype) == 256  # two a page

    def call(q, pool, tables, lengths, layer):
        return pa.paged_flash_attention(
            q, pool, None, tables, lengths, layer=layer, scale=0.1447,
            value_dim=512,
        )

    compiled = jax.jit(call).lower(
        s((B, Q, 64, 640), jnp.bfloat16), pool, s((B, 10), jnp.int32),
        s((B,), jnp.int32), s((1,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)
    assert ("paged_mla_decode" if Q == 1 else "paged_mla_fill") in compiled.as_text()


def _lowered_programs_script():
    """``scripts/lowered_programs.py`` as a module: what parses a Mosaic
    body out of a lowered program and hashes one without its locations."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "lowered_programs",
        os.path.join(os.path.dirname(__file__), "../../scripts/lowered_programs.py"),
    )
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    return script


def _mosaic_grids(lowered):
    """The ``iteration_bounds`` of each Mosaic call in a lowered program
    (None: an extent the call is handed at run time)."""
    script = _lowered_programs_script()
    grids = []
    for body in script._BODY.finditer(lowered.as_text()):
        (bounds,) = re.findall(
            r"iteration_bounds = array<i64: ([^>]*)>",
            script._without_locations(body.group(2)),
        )
        grids.append(
            tuple(None if int(b) < 0 else int(b) for b in bounds.split(","))
        )
    return grids


#: mode -> (q heads, pool, pages a row, keywords): the four decode calls
#: Mosaic names, at their cells' shapes
DECODE_MODES = {
    "paged_attn_decode": (12, (LAYERS, NB, 2, PAGE, HD), MB, {}),
    "paged_window_decode": (28, (6, 64, 4, 512, HD), 16, dict(window=4096)),
    "paged_mla_decode": (
        64, (5, 640, 1, 512, 640), 10, dict(scale=0.1447, value_dim=512),
    ),
    "paged_mla_window_decode": (
        64, (3, 256, 1, 512, 1152), 36,
        dict(scale=1 / 16.0, value_dim=1024, window=513),
    ),
}


#: scripts/lowered_programs.digest of each of them as the parent of PR 54
#: lowered it (commit e4a7bca; the same function run in a copy of that
#: tree): a decode call that brings no selection is the program it was
DECODE_CALLS = {
    "paged_attn_decode": "db68fac14af563c6d3d2e7dcc05039e22914b12d",
    "paged_window_decode": "79b2c004392c01b487b11ea7ad6e451265231c97",
    "paged_mla_decode": "ef295906c06cacd879d61d448daa64bbc7d03328",
    "paged_mla_window_decode": "22127f336e711ee98714270c279b1c18a0408f18",
}

#: the latent mode UNDER A SELECTION at the sparse cell's shapes (128 heads
#: on the stream, 36 pages of 512 x 640 a row: width 576 in 640, values the
#: first 512): (q heads, pool, pages a row, keywords)
MASKED_LATENT = (
    128, (2, 896, 1, 512, 640), 36, dict(scale=0.07, value_dim=512),
)


def _lower_decode_call(one_chip, mode, masked=False):
    Hq, pool_shape, pages, kw = mode
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def call(q, pool, tables, lengths, layer, *mask):
        return pa.paged_flash_attention(
            q, pool, None if "value_dim" in kw else pool, tables, lengths,
            layer=layer, **kw, **({"mask": mask[0]} if mask else {}),
        )

    return jax.jit(call).lower(
        s((64, 1, Hq, pool_shape[-1]), jnp.bfloat16), s(pool_shape, jnp.bfloat16),
        s((64, pages), jnp.int32), s((64,), jnp.int32), s((1,), jnp.int32),
        *([s((64, 1, pages * pool_shape[-2]), jnp.bool_)] if masked else []),
    )


@pytest.mark.parametrize("name", list(DECODE_MODES) + ["paged_mla_masked_decode"])
def test_a_decode_calls_first_extent_is_the_live_rows(one_chip, name):
    """A decode call (one query a row) hands Mosaic its grid's first
    extent at run time, the rows that hold pages, in the K/V, windowed,
    latent and windowed-latent modes alike, under the name it had, and
    under a selection (the sparse cell's decode step: the selection's
    block is one token's, ``(1, 1, G, 1, 512)``); the other two extents
    are static, and it compiles for the described v5e.  A call without a
    selection lowers to the text it had."""
    masked = name not in DECODE_MODES
    Hq, pool_shape, pages, kw = mode = MASKED_LATENT if masked else DECODE_MODES[name]
    lowered = _lower_decode_call(one_chip, mode, masked)
    span = pages if "window" not in kw else min(
        pages, pa.window_span_pages(pool_shape[-2], kw["window"])
    )
    G = pa.page_group(1, Hq, pool_shape, jnp.bfloat16, False, pages, masked)
    assert _mosaic_grids(lowered) == [(None, 1, -(-span // G))]
    assert name in lowered.compile().as_text()
    if masked:
        assert pa._plan_tiles(1, 128, 1, 512, 640, 2, False, 36, True) == (4, 1, 256)
    else:
        assert _lowered_programs_script().digest(lowered) == DECODE_CALLS[name]


#: scripts/lowered_programs.digest of a fill call of the paged kernel, as
#: the parent of PR 52 lowered it (commit f173069; the same function run
#: in a copy of that tree): a decode call's grid is bounded by its live
#: rows since, and a fill call is the program it was.  A change that MEANS
#: to move a fill call refreshes these with docs/lowered_programs.txt.
FILL_CALLS = {
    "paged_attn_fill": "9627b1d18d2d8e732a89024d608dfd7fb01b4f43",
    "paged_mla_fill": "9454cf3f912cdf768b439ac7afa23c0bc6c4472b",
    # (the sparse cell's fill under its selection, as the parent of PR 54
    # lowered it: a decode step's selection rides the same branch)
    "paged_mla_masked_fill": "8453d29c7d868149c8f93e5b33fb1fada6cda467",
}


@pytest.mark.parametrize("name", list(FILL_CALLS))
def test_a_fill_calls_lowered_text_is_what_it_was(one_chip, name):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    if name == "paged_attn_fill":
        def place(shape, dtype, _spec):
            return s(shape, dtype)

        lowered = jax.jit(_call_kernel).lower(
            *_paged_args("qwen2.5-1.5b", 16, 256, False, place)
        )
    else:
        masked = name == "paged_mla_masked_fill"
        Hq, pool_shape, pages, kw = (
            MASKED_LATENT if masked else DECODE_MODES["paged_mla_decode"]
        )

        def call(q, pool, tables, lengths, layer, *mask):
            return pa.paged_flash_attention(
                q, pool, None, tables, lengths, layer=layer, **kw,
                **({"mask": mask[0]} if mask else {}),
            )

        lowered = jax.jit(call).lower(
            s((1, 1024, Hq, 640), jnp.bfloat16), s(pool_shape, jnp.bfloat16),
            s((1, pages), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32),
            *([s((1, 1024, pages * 512), jnp.bool_)] if masked else []),
        )
    assert name in lowered.as_text()
    assert _lowered_programs_script().digest(lowered) == FILL_CALLS[name]


@pytest.mark.parametrize(
    "model,quantized",
    [("qwen2.5-1.5b", False), ("qwen2.5-7b", False), ("qwen2.5-7b", True)],
)
def test_shard_mapped_paged_kernel_compiles_tp2(
    tp2_mesh, monkeypatch, model, quantized
):
    """The TP serving path of models/paged._prefix_partials: the kernel
    under ``jax.shard_map`` over the kv-head axis of a 2-chip mesh."""
    # the backend here is the CPU; ask for the compiled (non-interpret)
    # kernel the way a TPU backend would
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)

    def place(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(tp2_mesh, spec)
        )

    args = _paged_args(model, 16, 1, quantized, place)

    def call(q, k, v, tables, lengths, layer, *scales):
        ks, vs = scales if scales else (None, None)
        # as the decode chunk does: the page plan once (for ONE shard's
        # heads), then the kernel with it
        plan = paged._prefix_plan(
            q.shape[1], q.shape[2], k, tables, lengths, True,
            mesh=tp2_mesh, kv_axis="model", quantized=quantized,
        )
        return paged._prefix_partials(
            q, k, v, tables, lengths, layer[0], True,
            mesh=tp2_mesh, kv_axis="model", k_scale=ks, v_scale=vs,
            plan=plan,
        )

    compiled = jax.jit(call).lower(*args).compile()
    _assert_kernel(compiled)
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize for a in args
    )
    assert per_chip < 0.6 * whole  # the pool really is split over chips


@pytest.mark.parametrize(
    "model,B,T",
    [
        ("qwen2.5-1.5b", 2, 2048),
        ("qwen2.5-1.5b", 1, 8192),
        ("qwen2.5-1.5b", 1, 4608),
        ("qwen2.5-7b", 1, 4096),
    ],
)
def test_flash_attention_fwd_bwd_compiles(one_chip, model, B, T):
    """The trainer's attention (this repository's flash kernels at block
    512, ``ops/flash_attention.py``) at the train cell's row lengths,
    forward and backward: three Mosaic calls, each under its stable
    name."""
    Hq, Hkv = HEADS[model]

    def loss(q, k, v, seg):
        return fa.flash_attention(q, k, v, seg).astype(jnp.float32).sum()

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(
            s((B, T, Hq, HD), jnp.bfloat16),
            s((B, T, Hkv, HD), jnp.bfloat16),
            s((B, T, Hkv, HD), jnp.bfloat16),
            s((B, T), jnp.int32),
        )
        .compile()
    )
    _assert_kernel(compiled)
    text = compiled.as_text()
    for name in ("flash_attn_fwd", "flash_attn_bwd_dq", "flash_attn_bwd_dkv"):
        assert name in text, name


#: scripts/lowered_programs.digest of the UNWINDOWED flash programs
#: (forward + backward at the dense train cell's head layout), as the parent
#: of PR 53 lowered them (commit d228c68; the same function run in a copy of
#: that tree): the kernels took a ``window`` since, and with ``window=None``
#: they are the programs they were, bit for bit
UNWINDOWED_FLASH = {
    (2, 2048): "0fd5e0cbddfdf017d16d8f397cc644498e6a837f",
    (1, 8192): "adf4b5b708a26a84037ec7023cd5b11b45333e27",
}


def _flash_loss(window):
    def loss(q, k, v, seg):
        out = fa.flash_attention(q, k, v, seg, window=window)
        return out.astype(jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def _flash_shapes(one_chip, B, T, Hq, Hkv):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (
        s((B, T, Hq, HD), jnp.bfloat16), s((B, T, Hkv, HD), jnp.bfloat16),
        s((B, T, Hkv, HD), jnp.bfloat16), s((B, T), jnp.int32),
    )


@pytest.mark.parametrize("B,T", sorted(UNWINDOWED_FLASH))
def test_the_unwindowed_flash_programs_lowered_text_is_the_parents(one_chip, B, T):
    lowered = jax.jit(_flash_loss(None)).lower(
        *_flash_shapes(one_chip, B, T, *HEADS["qwen2.5-1.5b"])
    )
    assert (
        _lowered_programs_script().digest(lowered) == UNWINDOWED_FLASH[B, T]
    )


@pytest.mark.parametrize(
    "Hq,T,window,band",
    [(64, 16384, 512, 2), (64, 9216, 512, 2), (48, 16384, None, 32), (64, 2048, 1300, 4)],
)
def test_windowed_flash_fwd_bwd_compiles_at_the_long_train_cells_heads(
    one_chip, Hq, T, window, band
):
    """laguna-xs.2's two kinds (64 window and 48 full heads on 8 KV heads
    of 128) at the train cell's row lengths: under a window the grid's
    minor axis is the band's blocks, and the three calls carry names of
    their own."""
    lowered = jax.jit(_flash_loss(window)).lower(
        *_flash_shapes(one_chip, 1, T, Hq, 8)
    )
    assert fa.band_blocks(T, window) == band
    assert _mosaic_grids(lowered) == [(1, Hq, T // 512, band)] * 3
    compiled = lowered.compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    stem = "flash_attn_window" if window else "flash_attn"
    for name in ("_fwd", "_bwd_dq", "_bwd_dkv"):
        assert stem + name in text, name
    if window:
        assert "flash_attn_fwd" not in text


def test_grouped_backward_compiles_at_the_long_train_cells_widths(one_chip):
    """The grouped product's backward over 32 held experts of 512 x 2,048
    at a 16,384-token micro-batch: passes of plain batched dots over the
    held pairs' tiles, float32 accumulators of the three dW, one buffer of
    rows for ``dx`` (0.57 GB in bfloat16), no pair dropped."""
    N, D, F, E, K = 16384, 2048, 512, 32, 8
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(x, w, gate, up, down, local):
        out, rounds = moe.grouped_expert_train(
            x, local, w, None, gate, up, down, E, "silu", moe.GROUP_ROWS
        )
        return out.astype(jnp.float32).sum(), rounds

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
        .lower(
            s((N, D), jnp.bfloat16), s((N, K), jnp.float32),
            *[s((E, F, D), jnp.float32)] * 3, s((N, K), jnp.int32),
        )
        .compile()
    )
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    print(f"grouped backward: total {total / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f} GB")
    assert total < 4e9, total


@pytest.mark.slow  # a minute of compiling: past tier-1's 60 s a test
def test_the_long_train_cells_largest_step_program_fits_one_chip(one_chip, monkeypatch):
    """``train-long-expert.laguna-xs.2``'s largest step program (ONE
    micro-batch of one 16,384-slot row: forward, backward through the flash
    kernels by kind and the grouped product, fused optimizer step over
    691.6 M parameters at 16 B each) at published widths.  The compiler
    admits it (it REFUSES a program past 15.75 G of hbm by its own count:
    two such micro-batches in one program read 16.90 G, PR 53); the count
    of ``memory_analysis`` is recorded."""
    import dataclasses
    import json
    import os

    import optax

    from areal_tpu.engine.optimizer import OptimizerConfig, make_optimizer
    from areal_tpu.interfaces import ppo_interface
    from benchmark.lib.program import model_config

    root = os.path.join(os.path.dirname(__file__), "../..")
    with open(os.path.join(root, "benchmark/configs/laguna-xs.2.json")) as f:
        cfg = model_config(json.load(f), "train")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert transformer.takes_flash(cfg, 16384, None)
    iface = ppo_interface.PPOActorInterface(
        n_minibatches=2, kl_ctl=0.0, disable_value=True,
        use_decoupled_loss=True, behav_imp_weight_cap=5.0, adv_norm=False,
    )
    tx = make_optimizer(OptimizerConfig(lr=1e-6), 10**6)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    place = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = place(
        jax.eval_shape(
            lambda k: hybrid.init_params(
                dataclasses.replace(cfg, dtype="float32"), k
            ),
            jax.random.PRNGKey(0),
        )
    )
    opt_state = place(jax.eval_shape(tx.init, params))
    T, S, f32, i32 = 16384, 8, jnp.float32, jnp.int32
    batch = {
        **{k: s((1, T), i32) for k in ("tokens", "positions", "seg_ids")},
        **{k: s((S,), i32) for k in ("seg_rows", "seg_starts", "seg_lens")},
        **{
            k: s((1, T), f32)
            for k in ("packed_logprobs", "prox_logp", "advantages", "ppo_loss_mask")
        },
        "seq_lens": s((1,), i32), "prompt_mask": s((1, T), jnp.bool_),
    }

    def step(params, opt_state, batch):
        def scalar(p):
            loss_sum, denom, stats = ppo_interface._actor_loss(p, cfg, batch, iface)
            return loss_sum, (denom, stats["moe_held_pairs_sum"])

        (loss_sum, (denom, pairs)), grads = jax.value_and_grad(
            scalar, has_aux=True
        )(params)
        grads = jax.tree.map(lambda g: g / jnp.maximum(denom, 1e-8), grads)
        norms = hybrid.grad_norms_by_group(grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, (loss_sum, pairs, norms)

    compiled = (
        jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, batch).compile()
    )
    m = compiled.memory_analysis()
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    print(
        f"laguna step [1, 16384]: {n / 1e6:.1f} M parameters, arguments "
        f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
        f"{m.temp_size_in_bytes / 1e9:.2f} GB by memory_analysis"
    )
    assert abs(n - 691.6e6) < 0.1e6
    text = compiled.as_text()
    for name in (
        "flash_attn_fwd", "flash_attn_bwd_dkv", "flash_attn_window_fwd",
        "flash_attn_window_bwd_dq", "flash_attn_window_bwd_dkv",
    ):
        assert name in text, name


@pytest.mark.parametrize("axes", [(1, 2, 1), (1, 1, 2)])
def test_flash_attention_compiles_on_a_two_chip_trainer_mesh(topo, axes):
    """A Mosaic kernel cannot be partitioned automatically: on the
    trainer's FSDP (or TP) mesh the flash kernel must run shard-mapped.
    Un-wrapped, this lowering raised NotImplementedError on four real
    chips (PR 21) — and compiles nowhere else, so it is guarded here."""
    Hq, Hkv = HEADS["qwen2.5-7b"]
    mesh = Mesh(
        np.array(topo.devices[:2]).reshape(axes), ("data", "fsdp", "model")
    )
    cfg = TransformerConfig(
        n_layers=1, hidden_dim=Hq * HD, n_q_heads=Hq, n_kv_heads=Hkv,
        head_dim=HD, intermediate_dim=256, vocab_size=256,
    )
    rows = P(("data", "fsdp"))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    def loss(q, k, v, seg):
        out = transformer._flash_attention(q, k, v, seg, cfg)
        return out.astype(jnp.float32).sum()

    transformer.set_ambient_mesh(mesh)  # reset by conftest after the test
    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(
            s((2, 2048, Hq, HD), jnp.bfloat16, rows),
            s((2, 2048, Hkv, HD), jnp.bfloat16, rows),
            s((2, 2048, Hkv, HD), jnp.bfloat16, rows),
            s((2, 2048), jnp.int32, rows),
        )
        .compile()
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize("model", sorted(HEADS))
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("Q", [1, 64, 256, 1024])
def test_vmem_plan_fits_budget(model, quantized, Q):
    """Pure shape math (no compiler): the planned tiles stay inside the
    budget and keep the query tile sublane-aligned."""
    Hq, Hkv = HEADS[model]
    r = Hq // Hkv
    itemsize = 1 if quantized else 2
    G, QT, tile = pa._plan_tiles(Q, r, Hkv, PAGE, HD, itemsize, quantized, MB)
    assert 1 <= G <= pa.PAGE_GROUP and 1 <= QT <= Q
    assert QT == Q or (QT * r) % 8 == 0
    assert PAGE % tile == 0 and tile % pa.LANES == 0
    assert PAGE // tile <= pa.MAX_PAGE_TILES
    need = pa.vmem_bytes_needed(Hkv, PAGE, HD, itemsize, quantized, G, QT * r)
    assert need <= pa.VMEM_BUDGET_BYTES < pa.VMEM_LIMIT_BYTES


def test_vmem_plan_raises_when_nothing_fits():
    """A shape the kernel cannot take raises where it is chosen."""
    with pytest.raises(ValueError, match="VMEM"):
        pa._plan_tiles(256, 8, 64, 4096, 256, 2, False, 4)


# --- the serving programs whole: where the KV pool's layout goes ---------

#: the rollout cell's engine (benchmark/traffic/rollout-longtail.json):
#: 192 pages of 1,024 tokens, rows of 4 pages, prefill chunks of 8 x 1,024
#: tokens, decode chunks of 64 steps for 64 rows
CELL_NB, FILL_F, FILL_C, DECODE_B, DECODE_W = 192, 8, 1024, 64, 64
#: hidden, intermediate, vocabulary, tied embedding, at the published widths
WIDTHS = {
    "qwen2.5-1.5b": (1536, 8960, 151936, True),
    "qwen2.5-7b": (3584, 18944, 152064, False),
}


def _serving_program_args(model, n_layers, quantized, place):
    """``(cfg, params, pool args, scale kwargs)`` of a serving program,
    shapes only; ``place(shape, dtype, spec)`` attaches the sharding."""
    Hq, Hkv = HEADS[model]
    hidden, inter, vocab, tied = WIDTHS[model]
    cfg = TransformerConfig(
        n_layers=n_layers, hidden_dim=hidden, n_q_heads=Hq, n_kv_heads=Hkv,
        head_dim=HD, intermediate_dim=inter, vocab_size=vocab,
        use_attention_bias=True, tied_embedding=tied, rotary_base=1e6,
    )
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(
        lambda a, spec: place(a.shape, jnp.bfloat16, spec),
        shapes, transformer.param_pspecs(cfg, shapes),
    )
    pool = place(
        (n_layers, CELL_NB, Hkv, PAGE, HD),
        jnp.int8 if quantized else jnp.bfloat16,
        P(None, None, "model"),
    )
    scale = place(
        (n_layers, CELL_NB, Hkv, PAGE), jnp.float32, P(None, None, "model")
    )
    scales = {"k_scale": scale, "v_scale": scale} if quantized else {}
    return cfg, params, pool, scales


def _instructions(compiled):
    """``(computation, name, result type, opcode)`` of every instruction of
    the optimized HLO."""
    computation = ""
    for line in compiled.as_text().splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            computation = ("ENTRY " if head.group(1) else "") + head.group(2)
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", line
        )
        if m:
            yield computation, m.group(1), m.group(2), m.group(3)


def _pool_copies(compiled, pool_shape):
    """``computation: instruction`` of every ``copy`` in the optimized
    HLO whose result has the pool's dimensions: a conversion of the
    whole pool between two layouts (``{minor_to_major:tiles}``), which
    no trace is needed to find (docs/observability.md)."""
    dims = "[" + ",".join(str(d) for d in pool_shape) + "]"
    return [
        f"{computation}: {name}"
        for computation, name, result, op in _instructions(compiled)
        if op == "copy" and re.match(r"\w+" + re.escape(dims), result)
    ]


def _weights_moved(compiled, weight_shape):
    """Where the optimized HLO makes a second array of a layer's expert
    weights (``[E_held, F, D]``): a ``copy`` or ``transpose`` with that
    result anywhere, or ANY instruction outside the fused computations
    whose result holds an array of that shape (a slice of the layer stack
    written out to feed a loop or a custom call, the loop that carries
    it: inside a product's fusion the slice is read where it lies)."""
    dims = ",".join(str(d) for d in weight_shape) + "]"
    dims = ("[" + dims, "[1," + dims)  # a layer's slice keeps the stack's axis
    found = []
    for computation, name, result, op in _instructions(compiled):
        if not any(d in result for d in dims):
            continue
        fused = "fused_computation" in computation or "fusion" in computation
        if op in ("copy", "transpose") or not (
            fused or op in ("parameter", "bitcast", "get-tuple-element")
        ):
            found.append(f"{computation}: {name} {op}")
    return found


def _assert_pool_stays_put(compiled, pool, temp_share):
    shape = pool.sharding.shard_shape(pool.shape)
    assert _pool_copies(compiled, shape) == []
    one_pool = int(np.prod(shape)) * jnp.dtype(pool.dtype).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_share * one_pool, (temp, one_pool)


SERVING_PROGRAMS = [
    # model, layers, int8 pool, chips
    ("qwen2.5-1.5b", 28, False, 1),
    ("qwen2.5-1.5b", 28, True, 1),
    ("qwen2.5-7b", 12, False, 1),
    ("qwen2.5-1.5b", 28, False, 2),
]


def _place_on(topo, chips):
    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        return None, lambda shape, dtype, _spec: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one
        )
    # the serving mesh of a TP engine: weights, pool and scale pools
    # split over "model" (engine/inference_server.py)
    mesh = Mesh(
        np.array(topo.devices[:chips]).reshape(1, chips), ("fsdp", "model")
    )
    return mesh, lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec)
    )


@pytest.mark.parametrize("model,n_layers,quantized,chips", SERVING_PROGRAMS)
def test_fill_program_keeps_the_pool_layout(
    topo, monkeypatch, model, n_layers, quantized, chips
):
    """``paged_fill_chunk`` whole, at the rollout cell's shape: the
    optimized HLO holds NO copy of a KV pool, in the layer loop or in
    ``ENTRY``, and its temporaries cannot hold one.

    On the parent of PR 28 the bf16 program at 28 layers held SIX
    (``temp_size_in_bytes`` 6.03 GB): ``copy.151`` / ``copy.152`` in
    ``ENTRY`` took each pool from its own layout
    ``{4,3,2,1,0:T(8,128)(2,1)}`` to the one the in-scan scatter wanted,
    ``{4,2,3,1,0:T(2,128)(2,1)}``; ``copy.224.remat2`` /
    ``copy.225.remat2`` INSIDE the layer loop's body converted each back
    for the kernel, so every layer rewrote all 28 layers of both pools
    (31% of the rollout cell's device time, ledger, PR 25); ``copy.155``
    / ``copy.156`` after the loop restored the donated outputs' layout.
    The 7B heads did not compile at 12 layers at all (16.24 of 15.75 GB,
    my chip run, PR 23).  What is left are the chunk's own
    temporaries, most of them the in-chunk scores (two float32
    ``[F, Hq, C, C]``, 0.8 GB at 8 x 1,024 tokens and 12 heads)."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    mesh, place = _place_on(topo, chips)
    cfg, params, pool, scales = _serving_program_args(
        model, n_layers, quantized, place
    )
    compiled = paged.paged_fill_chunk.lower(
        params, pool, pool, cfg,
        place((FILL_F, FILL_C), jnp.int32, P()),
        place((FILL_F,), jnp.int32, P()),
        place((FILL_F,), jnp.int32, P()),
        place((FILL_F, MB), jnp.int32, P()),
        use_kernel=True, mesh=mesh,
        kv_axis=None if mesh is None else "model", **scales,
    ).compile()
    _assert_kernel(compiled)
    _assert_pool_stays_put(compiled, pool, temp_share=1.0)


def _greedy(logits, _rng):
    return jnp.argmax(logits, -1).astype(jnp.int32), jnp.max(logits, -1)


def _never_stop(tokens):
    return jnp.zeros_like(tokens, bool)


@pytest.mark.parametrize("model,n_layers,quantized,chips", SERVING_PROGRAMS)
def test_decode_program_keeps_the_pool_layout(
    topo, monkeypatch, model, n_layers, quantized, chips
):
    """``paged_decode_chunk`` whole (64 rows, 64 steps): no copy of a KV
    pool anywhere.  The parent of PR 28 held four in ``ENTRY``, around
    its chunk-end scatter (``copy.126`` / ``copy.137`` to
    ``{4,3,1,2,0:T(8,128)(2,1)}``, ``copy.142`` / ``copy.143`` back;
    ``temp_size_in_bytes`` 2.94 GB), and none in the loop."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    mesh, place = _place_on(topo, chips)
    cfg, params, pool, scales = _serving_program_args(
        model, n_layers, quantized, place
    )

    def rows(dtype):
        return place((DECODE_B,), dtype, P())

    compiled = paged.paged_decode_chunk.lower(
        params, pool, pool, cfg,
        place((DECODE_B, MB), jnp.int32, P()),
        rows(jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32, P()),
        chunk_size=DECODE_W, sample_fn=_greedy, stop_fn=_never_stop,
        use_kernel=True, max_len=PAGE * MB, mesh=mesh,
        kv_axis=None if mesh is None else "model", **scales,
    ).compile()
    _assert_kernel(compiled)
    _assert_pool_stays_put(compiled, pool, temp_share=0.25)


# --- a stack stated by kind: state slots beside the pool, at published widths ---

#: granite-4.0-h-small as the cell runs it (benchmark/configs): one period
#: of 10 layers, 36 of 72 experts held, every width as published; the
#: cell's engine: 64 slots, 192 pages of 1,024 tokens, fills of 256 tokens
HYBRID_SLOTS, HYBRID_FILL_C = 64, 256
USABLE_HBM_BYTES = 15.75e9


def _hybrid_cell_args(one_chip):
    cfg = TransformerConfig(
        n_layers=10, hidden_dim=4096, n_q_heads=32, n_kv_heads=8,
        head_dim=HD, intermediate_dim=768, moe_intermediate_dim=768,
        shared_expert_dim=1536, vocab_size=100352, norm_eps=1e-5,
        tied_embedding=True, n_experts=72, n_experts_per_tok=10,
        moe_router="topk_softmax", moe_held_experts=36,
        layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
        mamba_n_heads=128, mamba_head_dim=64, mamba_d_state=128,
        embed_scale=12.0, attention_scale=0.0078125, residual_scale=0.22,
        logits_divisor=16.0, use_rope=False,
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    pool = place((1, CELL_NB, 8, PAGE, HD), jnp.bfloat16)
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, HYBRID_SLOTS))
    )
    return cfg, params, pool, ssm, conv, place


def _assert_state_and_pool_stay_put(compiled, pool, ssm):
    """No copy of the SSM state (2.4 GB) or of a KV pool anywhere in the
    optimized HLO, temporaries that cannot hold one, and the whole
    program inside one chip's memory."""
    assert _pool_copies(compiled, ssm.shape) == []
    assert _pool_copies(compiled, pool.shape) == []
    m = compiled.memory_analysis()
    state_bytes = int(np.prod(ssm.shape)) * 4
    assert m.temp_size_in_bytes < 0.25 * state_bytes, m.temp_size_in_bytes
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, total
    return total


# one prompt's chunk, two fills in a batch, three (padded to four)
@pytest.mark.parametrize("F", [1, 2, 4])
def test_hybrid_fill_program_fits_and_copies_no_state(
    one_chip, monkeypatch, F
):
    """``hybrid_fill_chunk`` whole at the cell's shapes.  What this holds
    down (described-v5e compiles, PR 31): rows of the stacked state read
    by a gather were lowered through lane-block slices of the WHOLE state
    (2.4 GB a Mamba layer); read by ``dynamic_slice``, the layout the SSD
    products prefer ran back to the stacked operand at F 4 and the state
    was converted whole inside the layer loop (hence the row-reader
    kernel, ``ssm_state_rows``); at F 1 the one-layer attention run is
    inlined and the kernel's read of the donated pool met the write loop
    (two copies a pool, hence the barrier before ``write_kv_runs``); with
    the conv tails carried through the layer loops the compiler held the
    whole ``conv`` array in its fast memory (``S(1)``) across the loops
    and their Mosaic calls, and on the chip three layers' tails of slots
    25-63 came back overwritten (hence the read before and the write
    after the stack: no loop of the program may carry ``conv``)."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pool, ssm, conv, place = _hybrid_cell_args(one_chip)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, pool, pool, ssm, conv, cfg,
        place((F, HYBRID_FILL_C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), place((F, MB), jnp.int32),
        place((F,), jnp.int32), use_kernel=True,
    ).compile()
    text = compiled.as_text()
    assert "paged_attn_fill" in text and "ssm_state_rows" in text
    # a stack with recurrent state multiplies every held expert at every
    # shape, `[4, 256]`'s 1,024 slots too (served rows came back non-finite
    # beside grouped fills, cause not known: ``moe.group_rows``): no round
    # of 256 rows an expert is laid out anywhere in the program
    assert moe.group_rows(cfg, F * HYBRID_FILL_C) == 0
    assert f"[36,{moe.GROUP_ROWS},4096]" not in text
    conv_dims = "[" + ",".join(str(d) for d in conv.shape) + "]"
    carried = [
        line for line in text.splitlines()
        if " while(" in line and conv_dims in line
    ]
    assert carried == [], carried[0][:200]
    # 9.93 GB of weights + 2.45 GB of state + 0.81 GB of pool, and little else
    total = _assert_state_and_pool_stay_put(compiled, pool, ssm)
    assert total > 13.0e9


def _keyed_greedy(logits, _rng, _positions, _seeds):
    return _greedy(logits, _rng)


def test_hybrid_decode_program_fits_and_updates_the_state_in_place(
    one_chip, monkeypatch
):
    """``hybrid_decode_chunk`` whole (64 rows, 64 steps): the state goes
    through the step loop and the layer scans as the kernel's aliased
    operand, no copy of it or of the pool; the held experts' weights are
    read where they lie (a ``ragged_dot`` custom call had each layer's
    sliced from the stack into a copy, 1.36 GB a layer and step); the
    conv tails, which this program does carry through its loops, stay in
    HBM (in the fill program they did not: see above)."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pool, ssm, conv, place = _hybrid_cell_args(one_chip)

    def rows(dtype):
        return place((HYBRID_SLOTS,), dtype)

    compiled = hybrid.hybrid_decode_chunk.lower(
        params, pool, pool, ssm, conv, cfg,
        place((HYBRID_SLOTS, MB), jnp.int32), rows(jnp.int32),
        rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=DECODE_W,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=PAGE * MB, row_seeds=rows(jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "ssm_state_update" in text and "paged_attn_decode" in text
    assert "ragged-dot" not in text
    conv_dims = ",".join(str(d) for d in conv.shape)
    assert not re.search(
        r"\[" + conv_dims + r"\]\{[^}]*S\(1\)\}", text
    ), "the conv state in the compiler's fast memory"
    # no copy of one layer's expert weights either
    assert _pool_copies(compiled, (36, 768, 4096)) == []
    _assert_state_and_pool_stay_put(compiled, pool, ssm)


# --- a stack of latent layers: latent pages, at published widths ---

#: gigachat3.1-702b-a36b as the cell runs it (benchmark/configs): one
#: dense + four expert layers, 16 of 256 experts held, 16,032 vocabulary
#: rows, every width as published; the cell's engine: 64 rows of 5,120,
#: 327,680 tokens of latent pages, decode chunks of 16 steps
LATENT_ROWS, LATENT_CTX, LATENT_POOL_TOKENS = 64, 5120, 327680
LATENT_CHUNK = 16


def _latent_cell_args(one_chip, page):
    cfg = TransformerConfig(
        n_layers=5, hidden_dim=7168, n_q_heads=64, n_kv_heads=64,
        head_dim=192, intermediate_dim=18432, moe_intermediate_dim=2048,
        shared_expert_dim=2048, vocab_size=16032, norm_eps=1e-6,
        rotary_base=100000.0, tied_embedding=False, n_experts=256,
        n_experts_per_tok=8, moe_router="sigmoid_group", moe_n_groups=8,
        moe_topk_groups=4, moe_routed_scale=2.5, moe_held_experts=16,
        layer_types=("latent",) * 5, n_dense_layers=1, q_lora_rank=1536,
        kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=192,
        rope_yarn_factor=64.0, rope_yarn_original_max=4096,
        rope_yarn_mscale=1.0, rope_yarn_mscale_all_dim=1.0,
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    k_shape, v_shape = paged.pool_shapes(cfg, LATENT_POOL_TOKENS // page, page)
    assert k_shape[2:] == (1, page, 640) and v_shape[-1] == 0
    pools = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, LATENT_ROWS))
    )
    return cfg, params, pools, ssm, conv, place


def _assert_latent_program_fits(compiled, pool):
    """No copy of the latent pool (2.1 GB) in the optimized HLO, and the
    whole program inside one chip's memory; returns (total, temporaries)
    by the compiler's count."""
    assert _pool_copies(compiled, pool.shape) == []
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, (total, m.temp_size_in_bytes)
    return total, m.temp_size_in_bytes


# the cell's largest fill: one prompt's chunk of 1,024; and two prompts'
# tails in one batch
@pytest.mark.parametrize("F,C", [(1, 1024), (2, 512), (4, 256)])
def test_latent_fill_program_fits_beside_weights_and_pool(
    one_chip, monkeypatch, F, C
):
    """``hybrid_fill_chunk`` whole at the latent cell's shapes: the
    prefix part is the Mosaic call ``paged_mla_fill`` (the name the
    readers match), the pool is an operand in its own layout (640
    columns: at 576 the compiler copied it whole before every call), and
    8.58 GB of weights + 2.10 GB of pool + the chunk's temporaries (the
    in-chunk scores of 64 heads are 0.27 GB in float32 at 1,024 tokens)
    fit one chip."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    page = 512
    cfg, params, (pool, vpool), ssm, conv, place = _latent_cell_args(one_chip, page)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, pool, vpool, ssm, conv, cfg,
        place((F, C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), place((F, LATENT_CTX // page), jnp.int32),
        place((F,), jnp.int32), use_kernel=True,
    ).compile()
    text = compiled.as_text()
    assert "paged_mla_fill" in text and "paged_attn" not in text
    assert "ragged-dot" not in text
    # the grouped product's rounds (``[1, 1024]``, ``[4, 256]``; ``[2,
    # 512]`` too) read a layer's held experts where they lie in the stack
    assert _weights_moved(compiled, (16, 2048, 7168)) == []
    total, temp = _assert_latent_program_fits(compiled, pool)
    # the cell's largest program no larger than with every held expert
    # computed for every token (PR 33: 11.17 / 11.10 / 11.03 GB; grouped
    # 11.17 / 11.13 / 11.03: a round's float32 rows at `[2, 512]`)
    assert 10.6e9 < total < {1: 11.18e9, 2: 11.14e9, 4: 11.04e9}[F], total
    print(f"latent fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_latent_decode_program_fits_and_reads_the_pool_in_place(
    one_chip, monkeypatch
):
    """``hybrid_decode_chunk`` whole (64 rows, 16 steps) over latent
    pages: ``paged_mla_decode`` by name, no copy of the pool, none of a
    layer's held experts, the whole inside one chip."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    page = 512
    cfg, params, (pool, vpool), ssm, conv, place = _latent_cell_args(one_chip, page)

    def rows(dtype):
        return place((LATENT_ROWS,), dtype)

    compiled = hybrid.hybrid_decode_chunk.lower(
        params, pool, vpool, ssm, conv, cfg,
        place((LATENT_ROWS, LATENT_CTX // page), jnp.int32), rows(jnp.int32),
        rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=LATENT_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=LATENT_CTX, row_seeds=rows(jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "paged_mla_decode" in text and "paged_attn" not in text
    assert "ragged-dot" not in text
    assert _pool_copies(compiled, (16, 2048, 7168)) == []
    total, temp = _assert_latent_program_fits(compiled, pool)
    print(f"latent decode: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


# --- window and global attention in one stack: two pools, published widths ---

#: smallthinker-21b-a3b as the cell runs it (benchmark/configs): two
#: periods [global, window x 3], all 64 experts held, the whole
#: vocabulary, every width as published; the cell's engine: 64 rows of
#: 10,240, 262,144 tokens of global pages and 196,608 of window pages,
#: decode chunks of 8 steps, pages of 512
WINDOW_ROWS, WINDOW_CTX, WINDOW_CHUNK = 64, 10240, 8
WINDOW_POOL_TOKENS = {"global": 262144, "window": 196608}


def _window_cell_args(one_chip, page):
    cfg = TransformerConfig(
        n_layers=8, hidden_dim=2560, n_q_heads=28, n_kv_heads=4,
        head_dim=128, intermediate_dim=768, moe_intermediate_dim=768,
        vocab_size=151936, max_position_embeddings=16384, norm_eps=1e-6,
        rotary_base=1.5e6, tied_embedding=False, activation="relu",
        n_experts=64, n_experts_per_tok=6, moe_router="topk_softmax",
        moe_router_input="attn", sliding_window=4096,
        layer_types=("attention", "window", "window", "window") * 2,
        rope_layers=(False, True, True, True) * 2,
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    pools = {}
    for kind, layers in (("global", None), ("window", cfg.n_window_layers)):
        k_shape, v_shape = paged.pool_shapes(
            cfg, WINDOW_POOL_TOKENS[kind] // page, page, layers
        )
        pools[kind] = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    assert pools["global"][0].shape[0] == 2 and pools["window"][0].shape[0] == 6
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, WINDOW_ROWS))
    )
    return cfg, params, pools, ssm, conv, place


def _assert_window_program_fits(compiled, pools):
    """No copy of either pool in the optimized HLO, and the whole program
    inside one chip's memory; returns (total, temporaries)."""
    for kind in pools:
        assert _pool_copies(compiled, pools[kind][0].shape) == [], kind
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, (total, m.temp_size_in_bytes)
    return total, m.temp_size_in_bytes


WINDOW_PAGE = 512


# the cell's largest fill: four rows of a chunk's width (a prompt's last
# piece with tails behind it), and one prompt's chunk; sixteen tails
# `[16, 256]` counted 13.34 GB at PR 40 (PERF.md section 4)
@pytest.mark.parametrize("F,C", [(4, 1024), (1, 1024)])
def test_window_fill_program_fits_beside_weights_and_two_pools(
    one_chip, monkeypatch, F, C
):
    """``hybrid_fill_chunk`` whole at the window cell's shapes: the global
    layers' prefix part is ``paged_attn_fill``, the window layers'
    ``paged_window_fill`` (the names the readers match), each pool an
    operand in its own layout, and 7.94 GB of weights + 3.49 GB of pools +
    the chunk's temporaries fit one chip."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _window_cell_args(one_chip, WINDOW_PAGE)
    table = place((F, WINDOW_CTX // WINDOW_PAGE), jnp.int32)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, *pools["global"], ssm, conv, cfg,
        place((F, C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), table, place((F,), jnp.int32),
        use_kernel=True, win_pools=pools["window"], win_tables=table,
    ).compile()
    text = compiled.as_text()
    assert "paged_attn_fill" in text and "paged_window_fill" in text
    assert "ragged-dot" not in text
    # the grouped product's rounds read a layer's 64 experts where they lie
    # in the stack: sliced before the rounds' loop they were written out,
    # 0.755 GB a layer (12.53-13.28 GB at `[1, 1024]`, PR 41)
    assert _weights_moved(compiled, (64, 768, 2560)) == []
    total, temp = _assert_window_program_fits(compiled, pools)
    # with every held expert computed for every token (PR 40): 13.77 GB at
    # `[4, 1024]` (four pieces of 1,024 tokens x 64 experts), 11.72 at
    # `[1, 1024]`; grouped 12.32 and 11.82 (one round's float32 rows,
    # `[64 x 256, 2560]`, are 0.10 GB more than the dense form's hidden;
    # the cell's largest program is 1.45 GB smaller)
    assert 11.4e9 < total < {4: 12.4e9, 1: 11.85e9}[F], total
    print(f"window fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_window_decode_program_fits_and_reads_both_pools_in_place(
    one_chip, monkeypatch
):
    """``hybrid_decode_chunk`` whole (64 rows, 8 steps) over the two
    pools: ``paged_attn_decode`` for the global layers and
    ``paged_window_decode`` for the window layers by name, no copy of
    either pool, none of a layer's 64 experts, the whole inside one
    chip."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _window_cell_args(one_chip, WINDOW_PAGE)

    def rows(dtype):
        return place((WINDOW_ROWS,), dtype)

    table = place((WINDOW_ROWS, WINDOW_CTX // WINDOW_PAGE), jnp.int32)
    compiled = hybrid.hybrid_decode_chunk.lower(
        params, *pools["global"], ssm, conv, cfg, table, rows(jnp.int32),
        rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=WINDOW_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=WINDOW_CTX, row_seeds=rows(jnp.int32),
        win_pools=pools["window"], win_tables=table,
    ).compile()
    text = compiled.as_text()
    assert "paged_attn_decode" in text and "paged_window_decode" in text
    assert "ragged-dot" not in text
    assert _pool_copies(compiled, (64, 768, 2560)) == []
    total, temp = _assert_window_program_fits(compiled, pools)
    print(f"window decode: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


# --- latent attention under an indexer's choice, and under a window at widths of its own ---

#: dots3-note-prev as the cell runs it (benchmark/configs): one dense + one
#: period (full, full, window, window, window), 16 of 256 experts held,
#: 19,008 vocabulary rows, every width as published; the cell's engine: 64
#: rows of 18,432, 458,752 tokens of latent pages with their index keys,
#: 131,072 of window latent pages, pages of 512, decode chunks of 8 steps
SPARSE_ROWS, SPARSE_CTX, SPARSE_CHUNK, SPARSE_PAGE = 64, 18432, 8, 512
SPARSE_POOL_TOKENS = {"global": 458752, "window": 131072}


def _sparse_cell_args(one_chip):
    cfg = TransformerConfig(
        n_layers=5, hidden_dim=5120, n_q_heads=128, n_kv_heads=128,
        head_dim=192, intermediate_dim=13824, moe_intermediate_dim=1536,
        shared_expert_dim=1536, vocab_size=19008, norm_eps=1e-5,
        rotary_base=8e7, tied_embedding=False, n_experts=256,
        n_experts_per_tok=8, moe_router="sigmoid_group", moe_n_groups=1,
        moe_topk_groups=1, moe_routed_scale=1.0, moe_held_experts=16,
        layer_types=("latent", "latent") + ("latent_window",) * 3,
        n_dense_layers=1, q_lora_rank=1024, kv_lora_rank=512,
        qk_rope_head_dim=64, v_head_dim=128, sliding_window=513,
        swa_n_q_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
        swa_head_dim=256, swa_qk_rope_head_dim=64, swa_v_head_dim=128,
        swa_rotary_base=5e4, index_n_heads=64, index_head_dim=128,
        index_topk=2048, attention_gate="headwise",
        swa_attention_gate="headwise", mla_lora_rescale=True,
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    pools = {}
    for kind, layers in (("global", None), ("window", cfg.n_window_layers)):
        k_shape, v_shape = paged.pool_shapes(
            cfg, SPARSE_POOL_TOKENS[kind] // SPARSE_PAGE, SPARSE_PAGE, layers
        )
        pools[kind] = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    assert pools["global"][0].shape == (2, 896, 1, 512, 640)
    assert pools["global"][1].shape == (2, 896, 1, 512, 128)  # the index keys
    assert pools["window"][0].shape == (3, 256, 1, 512, 1152)
    assert pools["window"][1].shape[-1] == 0
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, SPARSE_ROWS))
    )
    return cfg, params, pools, ssm, conv, place


@pytest.mark.parametrize("B,Q", [(64, 1), (1, 1024)])
def test_latent_kernel_under_the_window_compiles_at_the_cells_tile_plan(
    one_chip, B, Q
):
    """The paged kernel's latent mode WITH the window: ONE stream of pages
    of 512 x 1,152 columns (1,088 padded to nine lane tiles), values the
    first 1,024, 64 query heads on the stream, a window of 513 that spans
    at most three pages."""

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((3, 256, 1, 512, 1152), jnp.bfloat16)
    assert pa.window_span_pages(512, 513) == 2

    def call(q, pool, tables, lengths, layer):
        return pa.paged_flash_attention(
            q, pool, None, tables, lengths, layer=layer, scale=1 / 16.0,
            value_dim=1024, window=513,
        )

    compiled = jax.jit(call).lower(
        s((B, Q, 64, 1152), jnp.bfloat16), pool, s((B, 36), jnp.int32),
        s((B,), jnp.int32), s((1,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert ("paged_mla_window_decode" if Q == 1 else "paged_mla_window_fill") in text


@pytest.mark.parametrize("B", [1, 4])
def test_latent_kernel_under_a_selection_compiles_at_the_cells_tile_plan(
    one_chip, B
):
    """The paged kernel's latent mode WITH a selection: a fill chunk of
    1,024 queries of 128 heads (four tokens a query tile) over pages of
    512 x 640, the selection ``[B, 1024, 18432]`` laid out a block of four
    pages a step; the call without the operand keeps its name."""

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = pa._plan_tiles(1024, 128, 1, 512, 640, 2, False, 36, True)
    assert plan == pa._plan_tiles(1024, 128, 1, 512, 640, 2, False, 36) == (4, 4, 256)

    def call(q, pool, tables, lengths, layer, mask):
        return pa.paged_flash_attention(
            q, pool, None, tables, lengths, layer=layer, scale=0.07,
            value_dim=512, mask=mask,
        )

    args = (
        s((B, 1024, 128, 640), jnp.bfloat16),
        s((2, 896, 1, 512, 640), jnp.bfloat16), s((B, 36), jnp.int32),
        s((B,), jnp.int32), s((1,), jnp.int32),
    )
    compiled = jax.jit(call).lower(*args, s((B, 1024, 18432), jnp.bool_)).compile()
    _assert_kernel(compiled)
    assert "paged_mla_masked_fill" in compiled.as_text()
    if B == 1:
        bare = jax.jit(lambda *a: call(*a, None)).lower(*args).compile()
        assert "paged_mla_fill" in bare.as_text()
        assert "masked" not in bare.as_text()


def _assert_sparse_program_fits(compiled, pools):
    for kind in pools:
        for pool in pools[kind]:
            if pool.shape[-1]:
                assert _pool_copies(compiled, pool.shape) == [], (kind, pool.shape)
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, (total, m.temp_size_in_bytes)
    return total, m.temp_size_in_bytes


# the cell's largest fill: four rows of a chunk's width (one prompt's chunk,
# `[1, 1024]`, counts 8.60 GB with 1.13 of temporaries: not compiled here)
@pytest.mark.parametrize("F,C", [(4, 1024)])
def test_sparse_fill_program_fits_beside_weights_and_three_pools(
    one_chip, monkeypatch, F, C
):
    """``hybrid_fill_chunk`` whole at the sparse cell's shapes: the window
    layers' prefix part is ``paged_mla_window_fill`` (the name the readers
    match), a full layer's is ``paged_mla_masked_fill`` (its index scores
    and its mask are XLA's, the masked prefix the paged kernel's under the
    selection: no loop over pages in XLA, and no UNMASKED whole-prefix
    call), every pool an operand in its own layout, and
    5.15 GB of weights + 2.3 GB of pools + the chunk's temporaries (the
    in-chunk scores of 128 heads: 0.54 GB a row in float32) fit one
    chip."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _sparse_cell_args(one_chip)
    table = place((F, SPARSE_CTX // SPARSE_PAGE), jnp.int32)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, *pools["global"], ssm, conv, cfg,
        place((F, C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), table, place((F,), jnp.int32),
        use_kernel=True, win_pools=pools["window"], win_tables=table,
    ).compile()
    text = compiled.as_text()
    assert "paged_mla_window_fill" in text and "paged_mla_masked_fill" in text
    assert "paged_mla_fill" not in text and "paged_attn" not in text
    assert "ragged-dot" not in text
    total, temp = _assert_sparse_program_fits(compiled, pools)
    assert total < 13.6e9, total
    print(f"sparse fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


@pytest.mark.parametrize("pages", [36, 72])
def test_sparse_decode_program_fits_and_reads_every_pool_in_place(
    one_chip, monkeypatch, pages
):
    """``hybrid_decode_chunk`` whole (64 rows, 8 steps), keeping its chosen
    sets as the cell runs it: the window layers read
    ``paged_mla_window_decode``, and no call reads a full layer's whole
    context without a selection (no ``paged_mla_decode``).  Over the
    cell's table (36 pages: 9 x ``index_topk``,
    ``sparse_attention.decode_reads_masked``) a full layer attends its
    cached prefix UNDER ITS SELECTION in ``paged_mla_masked_decode`` (the
    index scores and the mask are XLA's): the program holds NO sort over
    the scores (no ``top_k``) and NO view of the latent pool as rows to
    gather.  Over a table twice as long (18 x) the chosen entries are
    gathered from the pool as it lies (no copy of it: the gather's operand
    is a bitcast of the pool), behind ``top_k``'s sorts."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _sparse_cell_args(one_chip)

    def rows(dtype):
        return place((SPARSE_ROWS,), dtype)

    table = place((SPARSE_ROWS, pages), jnp.int32)
    compiled = hybrid.hybrid_decode_chunk.lower(
        params, *pools["global"], ssm, conv, cfg, table, rows(jnp.int32),
        rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=SPARSE_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=pages * SPARSE_PAGE, row_seeds=rows(jnp.int32),
        win_pools=pools["window"], win_tables=table, keep_chosen=True,
    ).compile()
    text = compiled.as_text()
    masked = pages * SPARSE_PAGE == SPARSE_CTX
    assert "paged_mla_window_decode" in text
    assert ("paged_mla_masked_decode" in text) == masked
    assert "paged_mla_decode" not in text and "paged_attn" not in text
    assert "ragged-dot" not in text
    # (the sorts that stay under the mask: the page plans' over 64 rows,
    # the routers' over 256 experts)
    scored = f"[{SPARSE_ROWS},{pages * SPARSE_PAGE + SPARSE_CHUNK}]"
    sorts_of_scores = [
        name for _, name, result, op in _instructions(compiled)
        if op == "sort" and scored in result
    ]
    rows_of_the_pool = f"[{2 * 896 * SPARSE_PAGE},640]"
    assert bool(sorts_of_scores) == (rows_of_the_pool in text) == (not masked)
    total, temp = _assert_sparse_program_fits(compiled, pools)
    assert total < (10.5e9 if masked else 11.5e9), total
    print(f"sparse decode, {pages} pages a row: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


# --- a decoder-hybrid-decoder stack WHOLE: three cache kinds, one pool layer read by eight ---

#: phi-4-mini-flash-reasoning as the cell runs it (benchmark/configs): all
#: 32 layers at the published widths; the cell's engine: 64 rows of 10,240,
#: 294,912 tokens of whole-context pages (ONE layer), 114,688 of window
#: pages (8 layers), pages of 512, decode chunks of 3 steps
SHARED_ROWS, SHARED_CTX, SHARED_CHUNK, SHARED_PAGE = 64, 10240, 3, 512
SHARED_POOL_TOKENS = {"global": 294912, "window": 114688}


def _shared_cell_args(one_chip):
    cfg = TransformerConfig(
        n_layers=32, hidden_dim=2560, n_q_heads=40, n_kv_heads=20,
        head_dim=64, intermediate_dim=10240, vocab_size=200064,
        max_position_embeddings=262144, norm_type="layer", norm_eps=1e-5,
        use_attention_bias=True, tied_embedding=True, use_rope=False,
        sliding_window=512, diff_attention=True, n_dense_layers=32,
        layer_types=("mamba1", "window") * 8 + ("mamba1", "attention")
        + ("gmu", "cross") * 7,
        mamba_n_heads=5120, mamba_head_dim=1, mamba_d_state=16,
        mamba_d_conv=4, mamba_dt_rank=160,
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    pools = {}
    for kind, layers in (("global", None), ("window", cfg.n_window_layers)):
        k_shape, v_shape = paged.pool_shapes(
            cfg, SHARED_POOL_TOKENS[kind] // SHARED_PAGE, SHARED_PAGE, layers
        )
        pools[kind] = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    # ONE layer of whole-context pages, a pair's two heads of 64 as one of
    # 128 (no byte of padding), for the eight layers that read it
    assert pools["global"][0].shape == (1, 576, 10, SHARED_PAGE, 128)
    assert pools["window"][0].shape[0] == 8
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, SHARED_ROWS))
    )
    assert ssm.shape == (9, 64, 16, 5120) and conv.shape == (9, 3, 64, 5120)
    return cfg, params, pools, ssm, conv, place


def _assert_shared_program_fits(compiled, pools, ssm):
    """No copy of either pool or of the state in the optimized HLO, and
    the whole program inside one chip's memory; returns (total,
    temporaries)."""
    for kind in pools:
        assert _pool_copies(compiled, pools[kind][0].shape) == [], kind
    assert _pool_copies(compiled, ssm.shape) == []
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, (total, m.temp_size_in_bytes)
    return total, m.temp_size_in_bytes


def _arrays_of(compiled, shape):
    """Instructions outside the fused computations whose result holds an
    array of ``shape`` (any dtype), by opcode: what the program WRITES of
    that shape."""
    dims = "[" + ",".join(str(d) for d in shape) + "]"
    found = {}
    for computation, name, result, op in _instructions(compiled):
        fused = "fused_computation" in computation
        if dims in result and not fused and op not in (
            "parameter", "bitcast", "get-tuple-element", "tuple",
        ):
            found.setdefault(op, []).append(f"{computation}: {name}")
    return found


# the cell's largest fill (a prompt's last piece with the next prompt's
# first piece behind it) and one prompt's chunk
@pytest.mark.parametrize("F,C", [(2, 1024), (1, 1024)])
def test_shared_fill_program_fits_and_copies_no_pool_for_a_reading_layer(
    one_chip, monkeypatch, F, C
):
    """``hybrid_fill_chunk`` whole at the shared cell's shapes: 7.71 GB of
    weights + 6.21 GB of pools + 0.21 GB of state + the chunk's
    temporaries fit one chip; the eight global readers' prefix part is
    ``paged_attn_fill`` over the ONE pool layer and the window layers'
    ``paged_window_fill``; the shared layer's K and V of the chunk's own
    tokens exist ONCE (``[1, F, C, 10, 128]``, what the pool write takes):
    no copy of them a reading layer; the conv tails ride no loop."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _shared_cell_args(one_chip)
    table = place((F, SHARED_CTX // SHARED_PAGE), jnp.int32)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, *pools["global"], ssm, conv, cfg,
        place((F, C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), table, place((F,), jnp.int32),
        use_kernel=True, win_pools=pools["window"], win_tables=table,
    ).compile()
    text = compiled.as_text()
    for name in ("paged_attn_fill", "paged_window_fill", "ssm_state_rows"):
        assert name in text, name
    # the keep-nothing tail's one query a row reads its prefix under the
    # fill's name: the decode kernel's share of its roofline counts every
    # execution named ``paged_attn_decode`` in a traced slice
    assert "paged_attn_decode" not in text
    total, temp = _assert_shared_program_fits(compiled, pools, ssm)
    # not above what the program took with the tail on every position
    # (this compile at PR 43; the peak is a self-decoder layer's, so the
    # fall is small: 0.026 and 0.001 GB)
    assert temp <= {2: 660_966_400, 1: 301_635_584}[F], temp
    # the chunk's own K and V of the shared layer (2.6 MB a row each): at
    # most ONE relayout of each for the whole program, not one a reader
    once = _pool_copies(compiled, (1, F, C, 10, 128)) + _pool_copies(
        compiled, (F, C, 10, 128)
    )
    assert len(once) <= 2 and all(c.startswith("ENTRY") for c in once), once
    conv_dims = "[" + ",".join(str(d) for d in conv.shape) + "]"
    carried = [
        line for line in text.splitlines()
        if " while(" in line and conv_dims in line
    ]
    assert carried == [], carried[0][:200]
    assert 14.1e9 < total < {2: 14.9e9, 1: 14.6e9}[F], total
    print(f"shared fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_shared_decode_program_reads_one_pool_layer_from_eight_layers_in_place(
    one_chip, monkeypatch
):
    """``hybrid_decode_chunk`` whole (64 rows, 3 steps) over the three
    cache kinds: ``paged_attn_decode`` for the eight global readers,
    ``paged_window_decode`` for the window layers and
    ``ssm_state_update_m1`` for the Mamba-1 layers by name; no copy of a
    pool or of the state, no second array of the global pool, and the
    chunk's own K and V (``[9, W, 64, 10, 128]``: the window layers' and
    layer 17's, which the cross layers read where they lie) copied
    nowhere."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _shared_cell_args(one_chip)

    def rows(dtype):
        return place((SHARED_ROWS,), dtype)

    table = place((SHARED_ROWS, SHARED_CTX // SHARED_PAGE), jnp.int32)
    compiled = hybrid.hybrid_decode_chunk.lower(
        params, *pools["global"], ssm, conv, cfg, table, rows(jnp.int32),
        rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=SHARED_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=SHARED_CTX, row_seeds=rows(jnp.int32),
        win_pools=pools["window"], win_tables=table,
    ).compile()
    text = compiled.as_text()
    for name in ("paged_attn_decode", "paged_window_decode", "ssm_state_update_m1"):
        assert name in text, name
    total, temp = _assert_shared_program_fits(compiled, pools, ssm)
    # the chunk's own K and V (5.9 MB each) change layout where they enter
    # the step loop and a period's scan (6 copies at PR 42): once a step,
    # never once a reading layer (that would be 7 x 2 more)
    own = _pool_copies(compiled, (9, SHARED_CHUNK, SHARED_ROWS, 10, 128))
    assert len(own) <= 6, own
    # nothing but the pool write's loop and the program's own result holds
    # an array of the global pool's shape
    written = _arrays_of(compiled, pools["global"][0].shape)
    assert set(written) <= {"while", "dynamic-update-slice", "fusion", "custom-call"}, written
    assert 14.1e9 < total < 14.6e9, total
    print(f"shared decode: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_mamba1_state_kernel_compiles_at_the_cells_state(one_chip):
    """``ssm_state_update`` with ``a=`` over ``[9, 64, 16, 5120]``: a tile
    of 16 sublanes where Mamba-2 has 128, one lane block of 5,120."""
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    from areal_tpu.ops import ssm as ssm_ops

    S, N, C = 64, 16, 5120
    compiled = ssm_ops.ssm_state_update.lower(
        place((9, S, N, C), jnp.float32), place((), jnp.int32),
        place((S, C), jnp.float32), place((S, C), jnp.float32),
        place((S, N), jnp.float32), place((S, N), jnp.float32),
        place((S,), jnp.bool_), a=place((N, C), jnp.float32),
    ).compile()
    assert "ssm_state_update_m1" in compiled.as_text()
    assert _pool_copies(compiled, (9, S, N, C)) == []


# a decode row of 7 query heads a kv head, and a fill chunk's tile, at
# the cell's page size (256 and 1,024 compiled too when the page was
# timed: PERF.md section 4)
@pytest.mark.parametrize("page", [512])
@pytest.mark.parametrize("Q", [1, 1024])
def test_windowed_kernel_compiles_at_the_cells_head_grouping(one_chip, Q, page):
    """The windowed mode of the paged kernel by Mosaic, at 28 query heads
    over 4 kv heads of 128 (7 rows a kv head and query token: the tile plan
    no other cell runs), a layer-stacked pool, the window of 4,096."""
    place = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    B, MB = (64, WINDOW_CTX // page) if Q == 1 else (1, WINDOW_CTX // page)
    pool = place((6, 64, 4, page, HD), jnp.bfloat16)
    compiled = pa.paged_flash_attention.lower(
        place((B, Q, 28, HD), jnp.bfloat16), pool, pool,
        place((B, MB), jnp.int32), place((B,), jnp.int32),
        layer=place((), jnp.int32), window=4096,
        window_shift=place((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert ("paged_window_decode" if Q == 1 else "paged_window_fill") in text


# --- a stack of parallel layers: pages AND a state slot in every layer ---

#: falcon-h1-34b-instruct as the cell runs it (benchmark/configs): 8 of 72
#: layers, an eighth of the vocabulary, every width and multiplier as
#: published; the cell's engine: 64 rows of 5,120, 196,608 tokens of pages
PARALLEL_ROWS, PARALLEL_CTX, PARALLEL_PAGE = 64, 5120, 512
PARALLEL_POOL_TOKENS, PARALLEL_CHUNK = 196608, 8


def _parallel_cell_args(one_chip):
    cfg = TransformerConfig(
        n_layers=8, hidden_dim=5120, n_q_heads=20, n_kv_heads=4, head_dim=HD,
        intermediate_dim=21504, vocab_size=32640,
        max_position_embeddings=262144, norm_eps=1e-5, rotary_base=1e11,
        layer_types=("parallel",) * 8, n_dense_layers=8,
        mamba_n_heads=32, mamba_head_dim=128, mamba_d_state=256,
        mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=128,
        embed_scale=5.656854249492381, logits_divisor=128.0,
        attn_out_scale=0.0375, key_scale=0.011048543456039804,
        ssm_in_scale=0.25, ssm_out_scale=0.08838834764831845,
        ssm_scales=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                    0.3535533905932738),
        mlp_scales=(0.1767766952966369, 0.011160714285714284),
    )

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    k_shape, v_shape = paged.pool_shapes(
        cfg, PARALLEL_POOL_TOKENS // PARALLEL_PAGE, PARALLEL_PAGE
    )
    pools = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    assert k_shape == (8, 384, 4, PARALLEL_PAGE, HD)
    ssm, conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, PARALLEL_ROWS))
    )
    assert ssm.shape == (8, 64, 256, 4096) and conv.shape == (8, 3, 64, 5120)
    return cfg, params, pools, ssm, conv, place


# the cell's largest fill (a prompt's last piece with the next prompt's
# first piece behind it) and one prompt's chunk
@pytest.mark.parametrize("F,C", [(2, 1024), (1, 1024)])
def test_parallel_fill_program_fits_beside_weights_pages_and_state(
    one_chip, monkeypatch, F, C
):
    """``hybrid_fill_chunk`` whole at the parallel cell's shapes: 7.55 GB
    of weights + 3.22 GB of pages + 2.16 GB of state + the chunk's
    temporaries (the grouped SSD products, gate and up of 21,504 columns)
    fit one chip; every layer's prefix part is ``paged_attn_fill`` and its
    state rows come through ``ssm_state_rows``; no copy of the pools or of
    the state, and the conv tails ride no loop."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _parallel_cell_args(one_chip)
    compiled = hybrid.hybrid_fill_chunk.lower(
        params, *pools, ssm, conv, cfg, place((F, C), jnp.int32),
        place((F,), jnp.int32), place((F,), jnp.int32),
        place((F, PARALLEL_CTX // PARALLEL_PAGE), jnp.int32),
        place((F,), jnp.int32), use_kernel=True,
    ).compile()
    text = compiled.as_text()
    assert "paged_attn_fill" in text and "ssm_state_rows" in text
    conv_dims = "[" + ",".join(str(d) for d in conv.shape) + "]"
    carried = [
        line for line in text.splitlines()
        if " while(" in line and conv_dims in line
    ]
    assert carried == [], carried[0][:200]
    total = _assert_state_and_pool_stay_put(compiled, pools[0], ssm)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 12.9e9 < total, total
    print(f"parallel fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_parallel_decode_program_updates_pages_and_state_in_place(
    one_chip, monkeypatch
):
    """``hybrid_decode_chunk`` whole (64 rows): ``paged_attn_decode`` at 5
    query heads a KV head AND ``ssm_state_update`` with two groups in every
    layer by name; no copy of the pools or of the state; the conv tails
    stay in HBM.  What the compiler DOES copy, once a chunk and outside
    the step loop, is four weight stacks into the layout its loop wants:
    the Mamba-2 in-projection ``[8, 5120, 9248]`` (757 MB: 9,248 columns
    are 72.25 lane tiles, and the stack arrives with 5,120 minor), the
    queries' ``[8, 5120, 2560]`` (210 MB) and the keys' and values' ``[8,
    5120, 512]`` (42 MB each): 1.05 GB of temporaries and 3.1 ms of a
    chunk of 8 steps on the chip (``copy.125`` - ``copy.128`` of the traced
    run: 1.5% of busy time, my chip run, PR 46; PERF.md section 7)."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, ssm, conv, place = _parallel_cell_args(one_chip)

    def rows(dtype):
        return place((PARALLEL_ROWS,), dtype)

    compiled = hybrid.hybrid_decode_chunk.lower(
        params, *pools, ssm, conv, cfg,
        place((PARALLEL_ROWS, PARALLEL_CTX // PARALLEL_PAGE), jnp.int32),
        rows(jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=PARALLEL_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=PARALLEL_CTX, row_seeds=rows(jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "ssm_state_update" in text and "paged_attn_decode" in text
    conv_dims = ",".join(str(d) for d in conv.shape)
    assert not re.search(
        r"\[" + conv_dims + r"\]\{[^}]*S\(1\)\}", text
    ), "the conv state in the compiler's fast memory"
    assert _pool_copies(compiled, ssm.shape) == []
    assert _pool_copies(compiled, pools[0].shape) == []
    m = compiled.memory_analysis()
    temp = m.temp_size_in_bytes
    # the four weight stacks above and nothing of their size beside them
    assert temp < 1.10e9, temp
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes + temp
        - m.alias_size_in_bytes
    )
    assert 12.9e9 < total < 14.2e9, total
    print(f"parallel decode: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_grouped_state_kernel_compiles_at_the_cells_state(one_chip):
    """``ssm_state_update`` with two B/C groups over ``[8, 64, 256,
    4096]``: a tile of ``[256, 2048]`` float32 (2 MiB: 8 MiB of VMEM in and
    out, double-buffered), one lane block a group."""
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    from areal_tpu.ops import ssm as ssm_ops

    S, N, HP, G = 64, 256, 4096, 2
    compiled = ssm_ops.ssm_state_update.lower(
        place((8, S, N, HP), jnp.float32), place((), jnp.int32),
        place((S, HP), jnp.float32), place((S, HP), jnp.float32),
        place((S, G, N), jnp.float32), place((S, G, N), jnp.float32),
        place((S,), jnp.bool_),
    ).compile()
    assert "ssm_state_update" in compiled.as_text()
    assert _pool_copies(compiled, (8, S, N, HP)) == []


# --- the trainer's step whole: what the layout rule may ask of one chip ---


def test_train_step_at_the_train_cells_largest_shape_fits_one_chip(
    topo, one_chip, monkeypatch
):
    """The fused grad + optimizer program of the benchmark's train cell
    (``train-packed.qwen2.5-1.5b``: 10 layers at Qwen2.5-1.5B widths,
    float32 masters and Adam moments, the PPO actor's loss) at the LARGEST
    micro-batch ``[rows, T]`` the engine's layout rule gives the cell's
    three batches: arguments and temporaries fit the 15.75 GB a v5e leaves
    a program, by the compiler's count.  A change of the rule that lays out
    more slots a micro-batch shows here, before any chip: ``[1, 8192]``
    counts 14.146 GB = 8.417 arguments + 5.730 temporaries (0.6 MB over
    PR 39's parent, whose loss recomputed each chunk's logits: the loss
    that takes a chunk's gradient in place keeps ``d hidden`` and ``d
    head`` where the transposed scan kept them; 14.3 with the library's
    flash kernels, before PR 32), PR 30's parent's ``[3, 4096]`` 15.4,
    ``[4, 4096]`` would not pass.

    The micro-batch is compiled as a program of ONE.  A described chip has
    no memory limit for the scheduler to work to, so a program that
    accumulates over two micro-batches counts its second float32 gradient
    tree (2.8 GB) on top: the parent's ``[2, 3, 4096]`` counts 20.1 GB here
    and has run on the chip in every benchmark run since PR 23."""
    import json
    import os

    import areal_tpu.interfaces.ppo_interface  # noqa: F401 - "ppo_actor"
    from areal_tpu.api import model_api
    from areal_tpu.api.config import ModelInterfaceAbstraction
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.optimizer import OptimizerConfig, make_optimizer
    from areal_tpu.engine.train_engine import TrainEngine, plan_layout
    from benchmark.drivers import train_steps
    from benchmark.lib import lengths
    from benchmark.lib.program import model_config

    root = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")
    with open(os.path.join(root, "traffic", "train-packed.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(root, "configs", "qwen2.5-1.5b.json")) as f:
        cfg = model_config(json.load(f), "train")
    assert (cfg.n_layers, cfg.hidden_dim) == (10, WIDTHS["qwen2.5-1.5b"][0])
    iface = model_api.make_interface(
        ModelInterfaceAbstraction("ppo_actor", dict(traffic["interface"]))
    )
    mb_spec = MicroBatchSpec(max_tokens_per_mb=traffic["max_tokens_per_mb"])

    # an engine's layout and step without its arrays: nothing is attached
    eng = object.__new__(TrainEngine)
    eng.model_cfg, eng.pack_sequences, eng.pipe_size = cfg, True, 1
    eng.mesh = MeshSpec().make_mesh(topo.devices[:1])
    eng.tx = make_optimizer(OptimizerConfig(**traffic["optimizer"]), 10**6)
    eng._train_step_cache, eng._loss_head_products = {}, {}

    # the model takes the flash kernel, and the layout rule lengthens rows,
    # on a TPU only, and this process sees a CPU beside the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    largest = None
    keys = (
        train_steps.PER_TOKEN + train_steps.PER_TRANSITION
        + train_steps.PER_SEQUENCE
    )
    for k in range(traffic["distinct_batches"]):
        b = lengths.train_batch(traffic, 1, cfg.vocab_size, k)
        sample = SequenceSample.from_default(
            b["seqlens"],
            [f"s{i}" for i in range(len(b["seqlens"]))],
            {key: b[key] for key in keys},
        )
        iface._prepare_batch(sample)
        n = traffic["interface"]["n_minibatches"]
        for mb in sample.split(MicroBatchSpec(n_mbs=n))[0]:
            plan = plan_layout(
                cfg, mb.seqlens["packed_input_ids"], mb_spec, mesh=eng.mesh
            )
            size = (plan.rows * plan.row_len, plan.row_len)
            if largest is None or size > largest[0]:
                largest = (size, mb, plan)
    _, mb, plan = largest
    stacked, _ = eng._stack_batches(mb, plan, "packed_input_ids")
    stacked = {k: v[:1] for k, v in stacked.items()}

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    opt_state = jax.eval_shape(eng.tx.init, params)
    compiled = (
        eng._get_train_step(iface._loss_fn, 1)
        .lower(on_chip(params), on_chip(opt_state), on_chip(stacked))
        .compile()
    )
    _assert_kernel(compiled)
    m = compiled.memory_analysis()
    # params and optimizer state are donated: the outputs alias them
    need = (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )
    assert need < 15.75e9, (need / 1e9, plan)


# --- the snapshot slots of the fills kept for late siblings ---

#: cell -> (its arguments, the resident bytes the ledger's line of PR 46
#: gives it as ``hbm_peak_gb.rollout``): what eight snapshot slots and
#: eight kept logits rows are added to
KEPT_FILL_CELLS = {
    "hybrid": (_hybrid_cell_args, 13.33e9),
    "shared": (_shared_cell_args, 14.30e9),
    "parallel": (_parallel_cell_args, 13.04e9),
}


@pytest.mark.parametrize("cell", sorted(KEPT_FILL_CELLS))
def test_snapshot_slots_fit_and_are_copied_a_slot_at_a_time(one_chip, cell):
    """``copy_state_slots_between`` at a stateful cell's state shapes, both
    ways (a fill's end state to a snapshot slot; a snapshot to the late
    siblings' slots): the array written is updated in place, the array
    read is not copied, the temporaries hold a slot or two and never a
    state; and ``max_batch // 8`` snapshot slots with as many float32
    logits rows fit beside what the cell holds."""
    args, resident = KEPT_FILL_CELLS[cell]
    cfg, *_, ssm, conv, place = args(one_chip)
    rows = ssm.shape[1]
    snap_ssm, snap_conv = (
        place(a.shape, a.dtype)
        for a in jax.eval_shape(lambda: hybrid.state_zeros(cfg, rows // 8))
    )
    pairs = place((rows,), jnp.int32), place((rows,), jnp.int32)
    n = place((), jnp.int32)
    slot_bytes = hybrid.state_layout_bytes(cfg, 1)
    for frm, to in (
        ((ssm, conv), (snap_ssm, snap_conv)),
        ((snap_ssm, snap_conv), (ssm, conv)),
    ):
        compiled = hybrid.copy_state_slots_between.lower(
            *frm, *to, *pairs, n
        ).compile()
        for a in (ssm, conv, snap_ssm, snap_conv):
            assert _pool_copies(compiled, a.shape) == [], a.shape
        m = compiled.memory_analysis()
        assert m.temp_size_in_bytes < 2.5 * slot_bytes, (
            m.temp_size_in_bytes, slot_bytes,
        )
        # the written arrays are donated: the outputs are theirs (but
        # for the tuple that names them)
        assert m.output_size_in_bytes - m.alias_size_in_bytes < 4096
    kept = hybrid.state_layout_bytes(cfg, rows // 8)
    kept += (rows // 8) * cfg.vocab_size * 4
    print(f"{cell}: a slot {slot_bytes / 1e6:.1f} MB, kept {kept / 1e9:.3f} GB")
    assert resident + kept < USABLE_HBM_BYTES


# -- the looped cell (ouro-2.6b): 192 cache layers over 48 weight layers -----

LOOP_ROWS, LOOP_CTX, LOOP_CHUNK = 5, 1536, 8
LOOP_PAGE, LOOP_POOL_TOKENS = 128, 4864


def _loop_cell_args(one_chip):
    import json
    import os

    from benchmark.lib.program import model_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", "ouro-2.6b.json")) as f:
        cfg = model_config(json.load(f), "serve")
    with open(os.path.join(root, "benchmark", "traffic", "rollout-full-loop.json")) as f:
        eng = json.load(f)["engine"]
    # the shapes below ARE the cell's
    assert (
        eng["max_concurrent_batch"], eng["kv_cache_len"], eng["chunk_size"],
        eng["page_size"], eng["kv_pool_tokens"],
    ) == (LOOP_ROWS, LOOP_CTX, LOOP_CHUNK, LOOP_PAGE, LOOP_POOL_TOKENS)

    def place(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(
        lambda: transformer.init_params_in_dtype(cfg, jax.random.PRNGKey(0))
    )
    params = jax.tree.map(lambda a: place(a.shape, a.dtype), shapes)
    k_shape, v_shape = paged.pool_shapes(
        cfg, LOOP_POOL_TOKENS // LOOP_PAGE, LOOP_PAGE
    )
    assert k_shape == (192, LOOP_POOL_TOKENS // LOOP_PAGE, 16, LOOP_PAGE, HD)
    pools = place(k_shape, jnp.bfloat16), place(v_shape, jnp.bfloat16)
    return cfg, params, pools, place


def _loop_program_total(compiled, pool):
    """No copy of the pool (3.8 GB a side); the bytes the program stands
    at and its temporaries, by the compiler's count."""
    assert _pool_copies(compiled, pool.shape) == []
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < USABLE_HBM_BYTES, (total, m.temp_size_in_bytes)
    return total, m.temp_size_in_bytes


def test_paged_kernel_compiles_at_one_query_head_a_kv_head(one_chip):
    """The paged kernel's decode call at the looped cell's tile plan: 16 KV
    heads of 128 at ONE query head each, pages of 128 tokens out of a
    192-layer pool, the cell's 5 rows; and its fill call at 512 queries a
    row."""
    cfg, _, pools, place = _loop_cell_args(one_chip)
    assert pa.page_tile(pools[0].shape[-3:], jnp.bfloat16) == LOOP_PAGE
    for B, Q in ((LOOP_ROWS, 1), (1, 512)):
        compiled = jax.jit(_call_kernel).lower(
            place((B, Q, 16, HD), jnp.bfloat16), *pools,
            place((B, LOOP_CTX // LOOP_PAGE), jnp.int32),
            place((B,), jnp.int32), place((), jnp.int32),
        ).compile()
        _assert_kernel(compiled)


# one prompt's chunk of 512, and the widest batches the engine's GiB of
# stacked keys and values lets through (inference_server.FILL_KV_TEMP_BYTES)
@pytest.mark.parametrize("F,C", [(1, 512), (2, 256)])
def test_loop_fill_program_fits_beside_weights_and_a_192_layer_pool(
    one_chip, monkeypatch, F, C
):
    """``paged_fill_chunk`` whole at the looped cell's shapes: 5.34 GB of
    weights + 7.65 GB of pages + the chunk's temporaries (every cache
    layer's keys and values stacked: 0.8 GB for 512 positions; two weight
    stacks the compiler lays out again for its loop, 0.8 GB) fit one chip;
    ONE outer loop over the passes, the prefix part ``paged_attn_fill``;
    no copy of the pool."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, place = _loop_cell_args(one_chip)
    compiled = paged.paged_fill_chunk.lower(
        params, *pools, cfg, place((F, C), jnp.int32), place((F,), jnp.int32),
        place((F,), jnp.int32), place((F, LOOP_CTX // LOOP_PAGE), jnp.int32),
        use_kernel=True,
    ).compile()
    assert "paged_attn_fill" in compiled.as_text()
    total, temp = _loop_program_total(compiled, pools[0])
    assert 14.5e9 < total and temp < 2.1e9, (total, temp)
    print(f"loop fill F={F} C={C}: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")


def test_loop_decode_program_fits_and_reads_the_pool_in_place(
    one_chip, monkeypatch
):
    """``paged_decode_chunk`` whole (5 rows, 8 steps): ``paged_attn_decode``
    at one query head a KV head by name, once in the text (the layer body is
    compiled ONCE inside the loop over the passes, not four times); the
    chunk's window over the 192 cache layers is 63 MB; no copy of the pool.
    What the compiler does copy, once a chunk, is three of the four square
    weight stacks ``[48, 2048, 2048]`` into the layout its loop wants (1.2
    GB of temporaries: PERF.md section 7)."""
    monkeypatch.setattr(paged, "kernel_interpret", lambda: False)
    cfg, params, pools, place = _loop_cell_args(one_chip)

    def rows(dtype):
        return place((LOOP_ROWS,), dtype)

    compiled = paged.paged_decode_chunk.lower(
        params, *pools, cfg,
        place((LOOP_ROWS, LOOP_CTX // LOOP_PAGE), jnp.int32),
        rows(jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
        place((2,), jnp.uint32), chunk_size=LOOP_CHUNK,
        sample_fn=_keyed_greedy, stop_fn=_never_stop, use_kernel=True,
        max_len=LOOP_CTX, row_seeds=rows(jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_attn_decode" in text
    total, temp = _loop_program_total(compiled, pools[0])
    assert 14.0e9 < total and temp < 1.5e9, (total, temp)
    print(f"loop decode: total {total / 1e9:.2f} GB, temporaries {temp / 1e9:.2f} GB")
