"""``ops/ssm.py`` in interpret mode against plain ``jnp``: the decode
step of the Mamba-2 recurrence over layer-stacked state slots, in place,
live slots only; and the row reader a fill takes its states with."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import ssm

LM, S, N, HP = 3, 6, 16, 32


def _inputs(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        state=jax.random.normal(k[0], (LM, S, N, HP)),
        decay=jax.random.uniform(k[1], (S, HP)),
        dtx=jax.random.normal(k[2], (S, HP)),
        b=jax.random.normal(k[3], (S, N)),
        c=jax.random.normal(k[4], (S, N)),
    )


def _by_hand(state, layer, decay, dtx, b, c):
    """S <- S * decay + B (x) dtx; y = C^T S, slot by slot in numpy."""
    s = np.asarray(state[layer], np.float64)
    new = s * np.asarray(decay)[:, None, :] + (
        np.asarray(b)[:, :, None] * np.asarray(dtx)[:, None, :]
    )
    return np.einsum("snr,sn->sr", new, np.asarray(c)), new


@pytest.mark.parametrize(
    "live",
    [[1, 0, 1, 1, 0, 0], [1] * 6, [0] * 6, [0, 0, 0, 0, 0, 1], [1, 0, 0, 0, 0, 0]],
    ids=["mixed", "all", "none", "last_only", "first_only"],
)
@pytest.mark.parametrize("layer", [0, 2])
def test_state_update_kernel_is_the_recurrence_and_leaves_dead_slots_alone(
    live, layer
):
    x = _inputs()
    live = jnp.asarray(live, bool)
    y_want, s_want = _by_hand(x["state"], layer, x["decay"], x["dtx"], x["b"], x["c"])
    y, state = ssm.ssm_state_update(
        x["state"].copy(), jnp.int32(layer), x["decay"], x["dtx"], x["b"],
        x["c"], live, interpret=True,
    )
    y_ref, state_ref = ssm.ssm_state_update_reference(
        x["state"], layer, x["decay"], x["dtx"], x["b"], x["c"], live
    )
    lv = np.asarray(live)
    assert np.abs(np.asarray(state[layer])[lv] - s_want[lv]).max(initial=0) < 1e-5
    assert np.abs(np.asarray(y)[lv] - y_want[lv]).max(initial=0) < 1e-4
    # dead slots and every other layer: bit for bit what they were
    assert np.array_equal(np.asarray(state[layer])[~lv], np.asarray(x["state"][layer])[~lv])
    others = [l for l in range(LM) if l != layer]
    assert np.array_equal(np.asarray(state)[others], np.asarray(x["state"])[others])
    assert np.abs(np.asarray(state) - np.asarray(state_ref)).max() < 1e-5
    assert np.abs((np.asarray(y) - np.asarray(y_ref))[lv]).max(initial=0) < 1e-4


def test_state_update_tiles_a_wide_state_by_lane_blocks(monkeypatch):
    """Two lane blocks a slot: the dead steps of the grid must name the
    LAST block a live step touched, not the first."""
    monkeypatch.setattr(ssm, "LANE_BLOCK", 16)
    x = _inputs(1)
    live = jnp.asarray([0, 1, 0, 1, 0, 0], bool)
    y, state = ssm.ssm_state_update(
        x["state"].copy(), jnp.int32(1), x["decay"], x["dtx"], x["b"], x["c"],
        live, interpret=True,
    )
    y_ref, state_ref = ssm.ssm_state_update_reference(
        x["state"], 1, x["decay"], x["dtx"], x["b"], x["c"], live
    )
    assert np.abs(np.asarray(state) - np.asarray(state_ref)).max() < 1e-5
    lv = np.asarray(live)
    assert np.abs((np.asarray(y) - np.asarray(y_ref))[lv]).max() < 1e-4


@pytest.mark.parametrize("slots", [[4], [5, 0], [2, 2, 1, 3]])
def test_state_rows_reads_the_slots_of_one_layer(slots):
    state = _inputs(2)["state"]
    got = ssm.ssm_state_rows(
        state, jnp.int32(1), jnp.asarray(slots, jnp.int32), interpret=True
    )
    assert np.array_equal(np.asarray(got), np.asarray(state[1])[slots])


# --- Mamba-1: the decay a tile exp(dt[c] A[n, c]), formed inside the kernel ---


def _by_hand_m1(state, layer, dt, a, dtx, b, c):
    s = np.asarray(state[layer], np.float64)
    decay = np.exp(np.asarray(dt, np.float64)[:, None, :] * np.asarray(a, np.float64))
    new = s * decay + np.asarray(b)[:, :, None] * np.asarray(dtx)[:, None, :]
    return np.einsum("snr,sn->sr", new, np.asarray(c)), new


@pytest.mark.parametrize(
    "live", [[1, 0, 1, 1, 0, 0], [1] * 6, [0] * 6], ids=["mixed", "all", "none"]
)
@pytest.mark.parametrize("lane_block", [2048, 16], ids=["one_block", "two_blocks"])
def test_mamba1_state_update_forms_the_decay_tile_inside_the_kernel(
    monkeypatch, live, lane_block
):
    """``a=``: the kernel is handed dt and the layer's A ``[N, HP]`` and
    the decay differs by channel AND by state index; against numpy by hand
    and against ``ssm_state_update_reference``'s Mamba-1 twin; dead slots
    and other layers bit for bit what they were; one lane block and two."""
    monkeypatch.setattr(ssm, "LANE_BLOCK", lane_block)
    x = _inputs(2)
    dt = 0.5 * x["decay"]  # (0, 0.5)
    a = -jax.random.uniform(jax.random.PRNGKey(9), (N, HP), minval=1.0, maxval=16.0)
    live = jnp.asarray(live, bool)
    layer = 1
    y_want, s_want = _by_hand_m1(x["state"], layer, dt, a, x["dtx"], x["b"], x["c"])
    y, state = ssm.ssm_state_update(
        x["state"].copy(), jnp.int32(layer), dt, x["dtx"], x["b"], x["c"],
        live, interpret=True, a=a,
    )
    y_ref, state_ref = ssm.ssm_state_update_reference(
        x["state"], layer, dt, x["dtx"], x["b"], x["c"], live, a=a
    )
    lv = np.asarray(live)
    assert np.abs(np.asarray(state[layer])[lv] - s_want[lv]).max(initial=0) < 1e-5
    assert np.abs(np.asarray(y)[lv] - y_want[lv]).max(initial=0) < 1e-4
    assert np.array_equal(np.asarray(state[layer])[~lv], np.asarray(x["state"][layer])[~lv])
    others = [l for l in range(LM) if l != layer]
    assert np.array_equal(np.asarray(state)[others], np.asarray(x["state"])[others])
    assert np.abs(np.asarray(state) - np.asarray(state_ref)).max() < 1e-5
    assert np.abs((np.asarray(y) - np.asarray(y_ref))[lv]).max(initial=0) < 1e-4
    # and it is NOT a lane vector's decay: a head's scalar over its
    # channels (Mamba-2's form with the tile's first row) gives another state
    if lv.any():
        flat, _ = _by_hand(
            x["state"], layer, np.exp(np.asarray(dt) * np.asarray(a)[0]),
            x["dtx"], x["b"], x["c"],
        )
        assert np.abs(flat[lv] - y_want[lv]).max() > 1e-2


@pytest.mark.parametrize(
    "live",
    [[1, 0, 1, 1, 0, 0], [1] * 6, [0] * 6, [0, 0, 0, 0, 0, 1]],
    ids=["mixed", "all", "none", "last_only"],
)
@pytest.mark.parametrize(
    "groups, lane_block", [(2, 2048), (2, 8), (4, 2048)],
    ids=["a_block_a_group", "two_blocks_a_group", "four_groups"],
)
def test_state_update_hands_each_lane_block_its_groups_b_and_c(
    monkeypatch, live, groups, lane_block
):
    """B and C by GROUP (``[S, G, N]``): lanes ``[g HP/G, (g + 1) HP/G)``
    read group ``g``'s column, picked in the index map; against numpy slot
    by slot, against the jnp twin, dead slots and other layers bit for
    bit.  A lane block is a whole group (falcon_h1's 2,048 lanes) or part
    of one."""
    monkeypatch.setattr(ssm, "LANE_BLOCK", lane_block)
    x = _inputs(3)
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    b = jax.random.normal(k[0], (S, groups, N))
    c = jax.random.normal(k[1], (S, groups, N))
    live = jnp.asarray(live, bool)
    layer = 1
    lanes = HP // groups
    s = np.asarray(x["state"][layer], np.float64)
    b_l = np.repeat(np.asarray(b).transpose(0, 2, 1), lanes, axis=2)  # [S, N, HP]
    c_l = np.repeat(np.asarray(c).transpose(0, 2, 1), lanes, axis=2)
    s_want = s * np.asarray(x["decay"])[:, None, :] + b_l * np.asarray(x["dtx"])[:, None, :]
    y_want = (s_want * c_l).sum(1)
    y, state = ssm.ssm_state_update(
        x["state"].copy(), jnp.int32(layer), x["decay"], x["dtx"], b, c, live,
        interpret=True,
    )
    y_ref, state_ref = ssm.ssm_state_update_reference(
        x["state"], layer, x["decay"], x["dtx"], b, c, live
    )
    lv = np.asarray(live)
    assert np.abs(np.asarray(state[layer])[lv] - s_want[lv]).max(initial=0) < 1e-5
    assert np.abs(np.asarray(y)[lv] - y_want[lv]).max(initial=0) < 1e-4
    assert np.array_equal(np.asarray(state[layer])[~lv], np.asarray(x["state"][layer])[~lv])
    others = [l for l in range(LM) if l != layer]
    assert np.array_equal(np.asarray(state)[others], np.asarray(x["state"])[others])
    assert np.abs(np.asarray(state) - np.asarray(state_ref)).max() < 1e-5
    assert np.abs((np.asarray(y) - np.asarray(y_ref))[lv]).max(initial=0) < 1e-4
    # one group's column in every group's place is another result
    if lv.any():
        same = jnp.broadcast_to(b[:, :1], b.shape)
        y_one, _ = ssm.ssm_state_update_reference(
            x["state"], layer, x["decay"], x["dtx"], same, c, live
        )
        assert np.abs((np.asarray(y_one) - np.asarray(y_ref))[lv]).max() > 1e-2
