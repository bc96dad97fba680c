"""The decode step of a Mamba-2 (SSD) recurrence over per-slot state.

One sequence's state in one layer is ``S`` in ``R^{N x (H*P)}``: ``N`` the
state size (``mamba_d_state``), ``H*P`` the inner width (heads x head
size), laid out with the inner width along the lanes so that everything
that differs per channel (decay, input) is a lane vector and everything
that differs per state index (B, C) a sublane column.  One step is

    S <- S * decay + B (x) dtx          decay = exp(dt * A), dtx = dt * x
    y  = C^T S                          (the caller adds D * x)

which reads and writes the whole state once and does five vector
operations an element: bound by HBM bandwidth, 2 x 4 B x N x H*P a slot
and layer.  :func:`ssm_state_update` is the Pallas kernel for it, over the
engine's LAYER-STACKED state ``[Lm, slots, N, H*P]`` (the layer is picked
in the index map, like the paged pool's), in place
(``input_output_aliases``), visiting live slots only.

**Mamba-1** (``a=``): the decay differs by channel AND by state index,
``decay[n, c] = exp(dt[c] A[n, c])``, a TILE where Mamba-2's is a lane
vector (a head's scalar over its channels).  The same kernel under a
static branch: it is handed ``dt`` in the decay's place and the layer's
``A`` ``[N, H*P]`` (one block for every slot: fetched once a lane block)
and forms the tile inside, so no ``[slots, N, H*P]`` decay ever exists in
HBM (it would be a third pass over state-sized bytes).  One kernel and not
two because everything else is shared: the slot order, the skipped dead
slots, the aliasing, the tiles.  Its calls are named
``ssm_state_update_m1`` in the device trace.

**B/C groups** (``b`` / ``c`` of shape ``[S, G, N]``): heads ``[g H/G, (g
+ 1) H/G)`` read group ``g``'s B and C.  Heads lie along the lanes, so a
group is a run of ``H*P / G`` lanes; the lane block divides it, and the
column a grid step is handed is the group of its lane block (picked in
the index map like the slot and the layer: nothing else in the kernel
knows of groups).  With ONE group (``[S, N]``) the call is what it was.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of one state tile: [N=128, 2048] float32 is 1 MiB, in and out
#: double-buffered 4 MiB of VMEM
LANE_BLOCK = 2048


def _lane_block(width: int) -> int:
    return LANE_BLOCK if width % LANE_BLOCK == 0 else width


def _kernel(
    order_ref,  # scalar prefetch [S]: live slots first
    n_live_ref,  # scalar prefetch [1]
    layer_ref,  # scalar prefetch [1]
    s_ref,  # (1, 1, N, RB) the slot's state tile
    decay_ref,  # (1, 1, RB)
    dtx_ref,  # (1, 1, RB)
    b_ref,  # (1, N, 1)
    c_ref,  # (1, N, 1)
    *rest,  # [a_ref (N, RB) under ``per_state``,] then the two outputs:
    # y_ref (1, 1, RB); so_ref (1, 1, N, RB), the same buffer as the state
    per_state: bool = False,
):
    y_ref, so_ref = rest[-2:]
    i, j = pl.program_id(0), pl.program_id(1)
    n_live = n_live_ref[0]

    @pl.when(i < n_live)
    def _live():
        if per_state:  # decay_ref holds dt: the tile exp(dt[c] A[n, c])
            decay = jnp.exp(decay_ref[0] * rest[0][...])
            s = s_ref[0, 0] * decay + b_ref[0] * dtx_ref[0]
        else:
            s = s_ref[0, 0] * decay_ref[0] + b_ref[0] * dtx_ref[0]  # [N, RB]
        so_ref[0, 0] = s
        y_ref[0] = jnp.sum(s * c_ref[0], axis=0, keepdims=True)

    # with no live slot every grid step names the one tile that step 0
    # fetched, and it is written back when the grid ends: as it came
    @pl.when((n_live == 0) & (i == 0) & (j == 0))
    def _none():
        so_ref[0, 0] = s_ref[0, 0]
        y_ref[0] = jnp.zeros_like(y_ref[0])


def _visited(i, j, order_ref, n_live_ref, n_j):
    """(slot, lane block) grid step (i, j) works on.  Steps past the live
    slots name the LAST tile a live step touched: no fetch, no write-back
    of their own (Pallas moves a block only when its index changes)."""
    n_live = n_live_ref[0]
    slot = order_ref[jnp.minimum(i, jnp.maximum(n_live - 1, 0))]
    return slot, jnp.where(i < n_live, j, n_j - 1)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def ssm_state_update(
    state: jax.Array,  # [Lm, S, N, HP] float32
    layer: jax.Array,  # [] or [1] int32: which of the Lm layers
    decay: jax.Array,  # [S, HP] float32: exp(dt * A), repeated over P
    dtx: jax.Array,  # [S, HP] float32: dt * x
    b: jax.Array,  # [S, N] float32, or [S, G, N]: one a group of heads
    c: jax.Array,  # as b
    live: jax.Array,  # [S] bool: slots that take this step
    interpret: bool = False,
    a: Optional[jax.Array] = None,  # [N, HP] float32 (< 0): Mamba-1
) -> Tuple[jax.Array, jax.Array]:
    """One recurrence step of layer ``layer`` for every live slot, in
    place.  Returns ``(y [S, HP] float32, state)``; ``y`` of a dead slot
    is NOT written (mask it), its state is not touched.  With ``a``,
    ``decay`` holds ``dt`` and the decay is ``exp(dt[c] a[n, c])``
    (module docstring)."""
    _, S, N, HP = state.shape
    G = b.shape[1] if b.ndim == 3 else 1
    assert HP % G == 0 and (a is None or G == 1), (HP, G)
    RB = _lane_block(HP // G)
    n_j = HP // RB
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def state_map(i, j, order_ref, n_live_ref, layer_ref):
        slot, jj = _visited(i, j, order_ref, n_live_ref, n_j)
        return layer_ref[0], slot, 0, jj

    def lane_map(i, j, order_ref, n_live_ref, layer_ref):
        slot, jj = _visited(i, j, order_ref, n_live_ref, n_j)
        return slot, 0, jj

    def col_map(i, j, order_ref, n_live_ref, layer_ref):
        slot, jj = _visited(i, j, order_ref, n_live_ref, n_j)
        if G == 1:
            return slot, 0, 0
        # [S, G*N, 1] in blocks of N: the block's number is the group
        return slot, jj // (n_j // G), 0

    def a_map(i, j, order_ref, n_live_ref, layer_ref):
        _, jj = _visited(i, j, order_ref, n_live_ref, n_j)
        return 0, jj

    lane_spec = pl.BlockSpec((1, 1, RB), lane_map)
    col_spec = pl.BlockSpec((1, N, 1), col_map)
    state_spec = pl.BlockSpec((1, 1, N, RB), state_map)
    in_specs = [state_spec, lane_spec, lane_spec, col_spec, col_spec]
    kernel, name, more = _kernel, "ssm_state_update", ()
    if a is not None:
        kernel = functools.partial(_kernel, per_state=True)
        name, more = "ssm_state_update_m1", (a.astype(jnp.float32),)
        in_specs = in_specs + [pl.BlockSpec((N, RB), a_map)]
    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, n_j),
            in_specs=in_specs,
            out_specs=[lane_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, HP), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(
        order, n_live, layer, state,
        decay.reshape(S, 1, HP), dtx.reshape(S, 1, HP),
        b.reshape(S, G * N, 1), c.reshape(S, G * N, 1), *more,
    )
    return y.reshape(S, HP), state


def _rows_kernel(slots_ref, layer_ref, s_ref, o_ref):
    o_ref[0] = s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_rows(
    state: jax.Array,  # [Lm, S, N, HP]
    layer: jax.Array,  # [] or [1] int32
    slots: jax.Array,  # [F] int32
    interpret: bool = False,
) -> jax.Array:
    """``state[layer, slots]`` as ``[F, N, HP]``, tile by tile.  What a
    fill reads of the stacked state: as a kernel, so that the layout the
    reader's products prefer for these rows stops HERE.  Read by
    ``dynamic_slice`` (or a gather), that preference runs back to the
    stacked operand, and the TPU compiler then converts the WHOLE state,
    2.4 GB, to it inside the layer loop (described-v5e compile, PR 31)."""
    _, _, N, HP = state.shape
    F = slots.shape[0]
    RB = _lane_block(HP)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(F, HP // RB),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, N, RB),
                    lambda i, j, slots_ref, layer_ref: (
                        layer_ref[0], slots_ref[i], 0, j
                    ),
                )
            ],
            out_specs=pl.BlockSpec(
                (1, N, RB), lambda i, j, slots_ref, layer_ref: (i, 0, j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((F, N, HP), state.dtype),
        interpret=interpret,
        name="ssm_state_rows",
    )(slots.astype(jnp.int32), layer, state)


def ssm_state_update_reference(state, layer, decay, dtx, b, c, live, a=None):
    """The same step in plain ``jnp`` (same contract, but ``y`` of a dead
    slot is what its untouched state gives)."""
    layer = jnp.asarray(layer, jnp.int32).reshape(())
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    decay = decay[:, None, :]
    if a is not None:  # Mamba-1: ``decay`` holds dt
        decay = jnp.exp(decay * a)
    if b.ndim == 3:  # [S, G, N]: each lane reads its group's column
        lanes = state.shape[-1] // b.shape[1]
        b, c = (
            jnp.repeat(t.swapaxes(1, 2), lanes, axis=2) for t in (b, c)
        )  # [S, N, HP]
    else:
        b, c = b[:, :, None], c[:, :, None]
    new = s * decay + b * dtx[:, None, :]
    new = jnp.where(live[:, None, None], new, s)
    y = jnp.sum(new * c, axis=1)
    return y, jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)
