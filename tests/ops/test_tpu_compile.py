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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.models import paged, transformer
from areal_tpu.models.config import TransformerConfig
from areal_tpu.ops import flash_attention as fa
from areal_tpu.ops import paged_attention as pa

#: (n_q_heads, n_kv_heads) at head_dim 128
HEADS = {"qwen2.5-1.5b": (12, 2), "qwen2.5-7b": (28, 4)}
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
    ],
)
def test_paged_kernel_compiles(one_chip, model, B, Q, quantized):
    def place(shape, dtype, _spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = _paged_args(model, B, Q, quantized, place)
    compiled = jax.jit(_call_kernel).lower(*args).compile()
    _assert_kernel(compiled)


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
        ("qwen2.5-7b", 1, 4096),
    ],
)
def test_flash_attention_fwd_bwd_compiles(one_chip, model, B, T):
    """The trainer's attention (JAX-shipped flash kernel at block 512) at
    packed-row bucket lengths, forward and backward."""
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
    G, QT = pa._plan_tiles(Q, r, Hkv, PAGE, HD, itemsize, quantized, MB)
    assert 1 <= G <= pa.PAGE_GROUP and 1 <= QT <= Q
    assert QT == Q or (QT * r) % 8 == 0
    need = pa.vmem_bytes_needed(Hkv, PAGE, HD, itemsize, quantized, G, QT * r)
    assert need <= pa.VMEM_BUDGET_BYTES < pa.VMEM_LIMIT_BYTES


def test_vmem_plan_raises_when_nothing_fits():
    """A shape the kernel cannot take raises where it is chosen."""
    with pytest.raises(ValueError, match="VMEM"):
        pa._plan_tiles(256, 8, 64, 4096, 256, 2, False, 4)
