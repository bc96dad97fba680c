"""Zero-downtime weight sync: staged sharded restore + pointer-flip
commit (ISSUE 8's tentpole).

The contract under test: ``stage_weights`` prepares a device-resident
tree while decode continues and ``commit_staged`` swaps it in with the
exact semantics of the legacy ``update_weights`` — ring drained under
the old weights, prefix cache flushed, in-flight KV recomputed, version
stamps intact — while the interrupting window shrinks to the pointer
flip.  Around that core: the version-consistent commit barrier (commit
of a different version than staged must fail before anything flips),
interplay with chunked prefill in flight, staged restore through an
actual published orbax snapshot, and the 2-chip-mesh arm restoring
straight onto serving shardings (slow-marked: tier-1 keeps the
single-chip arms).
"""

import os
import threading

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine import checkpoint
from areal_tpu.engine.generation import generate_tokens
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

EOS = 5

_cfg = tiny_config(vocab_size=64, max_position_embeddings=256)
_params = transformer.init_params(_cfg, jax.random.PRNGKey(0))
_params2 = transformer.init_params(_cfg, jax.random.PRNGKey(42))


def make_engine(mode="paged", params=None, **kw):
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
    )
    if mode == "paged":
        defaults.update(
            cache_mode="paged", page_size=16, prefill_chunk_tokens=32
        )
    else:
        defaults.update(cache_mode="dense")
    defaults.update(kw)
    return ContinuousBatchingEngine(
        _cfg, _params if params is None else params, **defaults
    )


def _req(qid, prompt, budget):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(prompt), input_ids=list(prompt),
        gconfig=GenerationHyperparameters(
            max_new_tokens=budget, greedy=True
        ),
    )


def run_until_done(eng, max_steps=600):
    for _ in range(max_steps):
        if not eng.has_work:
            break
        eng.step()
    assert not eng.has_work, "engine did not drain"


def ref_ids(prompt, budget, params=None):
    return generate_tokens(
        _params if params is None else params, _cfg, [list(prompt)],
        GenerationHyperparameters(max_new_tokens=budget, greedy=True),
        EOS, jax.random.PRNGKey(1),
    )[0]["output_ids"]


def assert_v0_prefix_v1_tail(got, prompt, budget, params2=_params2):
    """The output must split cleanly into a v0-greedy prefix and a
    v1-greedy tail (the interruptible-swap invariant).  The split is the
    longest common prefix with the v0 stream, verified by ONE v1-greedy
    continuation — valid because greedy decode is suffix-consistent: if
    ``got[k:]`` is the v1 continuation of ``prompt + got[:k]`` then so
    is every later suffix of it, including the one starting at the lcp
    (which can only overshoot k through v0/v1 agreement)."""
    v0 = ref_ids(prompt, budget)
    split = 0
    while (
        split < len(got) and split < len(v0) and got[split] == v0[split]
    ):
        split += 1
    if split < len(got):
        tail = generate_tokens(
            params2, _cfg, [list(prompt) + got[:split]],
            GenerationHyperparameters(
                max_new_tokens=len(got) - split, greedy=True
            ),
            EOS, jax.random.PRNGKey(2),
        )[0]["output_ids"]
        assert got[split:] == tail[: len(got) - split], (got, v0, split)
    return split


# -- stage/commit API unit ----------------------------------------------------


def test_commit_without_stage_raises():
    eng = make_engine(mode="dense")
    with pytest.raises(RuntimeError, match="no staged weights"):
        eng.commit_staged()


def test_commit_version_mismatch_fails_before_flip():
    """The fleet's commit barrier is version-consistent: committing a
    different version than was staged must fail with NOTHING flipped."""
    eng = make_engine(mode="dense")
    eng.stage_weights(_params2, version=3)
    with pytest.raises(RuntimeError, match="v3"):
        eng.commit_staged(expected_version=4)
    assert eng.version == 0
    assert eng.staged_version == 3  # tree intact; a correct commit works
    assert eng.commit_staged(expected_version=3) == 0
    eng.step()
    assert eng.version == 3


def test_discard_staged_drops_uncommitted_tree():
    eng = make_engine(mode="dense")
    eng.stage_weights(_params2, version=1)
    eng.discard_staged()
    assert eng.staged_version is None
    with pytest.raises(RuntimeError, match="no staged weights"):
        eng.commit_staged()


def test_stage_is_nonblocking_for_decode_and_commit_is_pointer_flip():
    """Staging from another thread never interrupts the decode loop, and
    the commit produces the v0-prefix/v1-tail split with the swap
    counters attributing stage vs pause time."""
    eng = make_engine(mode="dense")
    prompt = [7, 8, 9]
    budget = 100  # enough that the row survives staging + the ring drain
    eng.submit(_req("q0", prompt, budget))
    for _ in range(3):
        eng.step()
    done = threading.Event()

    def _stage():
        eng.stage_weights(_params2, version=1)
        done.set()

    threading.Thread(target=_stage, daemon=True).start()
    while not done.is_set():
        eng.step()  # decode continues while the tree stages
    assert eng.staged_version == 1
    assert eng.commit_staged(expected_version=1) == 1
    run_until_done(eng)
    out = eng.wait_result("q0", timeout=5)
    assert out.version_start == 0 and out.version_end == 1
    split = assert_v0_prefix_v1_tail(list(out.output_ids), prompt, budget)
    assert 0 < split < len(out.output_ids)
    stats = eng.swap_stats()
    assert stats["swaps_total"] == 1
    assert stats["swaps_staged_total"] == 1
    assert stats["stage_s"] > 0.0
    assert stats["pause_s"] > 0.0


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_staged_commit_matches_full_reload_stream(mode):
    """Pointer-flip and full-reload swaps at the SAME point must emit
    identical streams — the staged path changes only the downtime."""

    def run(swap):
        eng = make_engine(mode=mode)
        eng.submit(_req("q0", [11, 12, 13], 20))
        for _ in range(2):
            eng.step()
        swap(eng)
        run_until_done(eng)
        return eng.wait_result("q0", timeout=5).output_ids

    def staged(eng):
        eng.stage_weights(_params2, version=1)
        eng.commit_staged(expected_version=1)

    def full(eng):
        eng.update_weights(_params2, version=1)

    assert run(staged) == run(full)


# -- interplay: chunked prefill / prefix cache --------------------------------


def test_staged_commit_mid_chunked_prefill_restarts_fill_under_v1():
    """Commit while a long prompt is mid-chunked-prefill: the fill
    restarts from scratch under the new weights, so the output matches a
    fresh engine running the new weights end to end."""
    prompt = list(np.arange(90) % 50 + 6)  # 3 prefill chunks at 32
    eng = make_engine()  # paged
    # a decoding row first: with decode active, _advance_fill stops after
    # ONE chunk per step (the interleave), so the long prompt is caught
    # genuinely mid-fill
    eng.submit(_req("d0", [7, 8, 9], 60))
    for _ in range(2):
        eng.step()
    eng.submit(_req("q0", prompt, 10))
    eng.step()
    fill = next((f for f in eng._filling if f.targets), None)
    assert fill is not None and 0 < fill.fill_pos < len(prompt), (
        "prompt must be caught mid-chunked-prefill"
    )
    eng.stage_weights(_params2, version=1)
    eng.commit_staged(expected_version=1)
    run_until_done(eng)
    got = eng.wait_result("q0", timeout=5)
    fresh = make_engine(params=_params2)
    fresh.submit(_req("f0", prompt, 10))
    run_until_done(fresh)
    assert got.output_ids == fresh.wait_result("f0", timeout=5).output_ids
    assert got.version_end == 1


def test_staged_commit_flushes_prefix_cache_and_fresh_replay_matches():
    """The staged commit keeps the legacy apply invariants: the radix
    cache flushes (no pre-swap KV survives) and a post-swap turn matches
    a fresh engine running the new weights."""
    eng = make_engine(prefix_cache=True, prefix_cache_min_tokens=1)
    conv = list(np.arange(40) % 50 + 6)
    eng.submit(_req("t0", conv, 8))
    run_until_done(eng)
    first = eng.wait_result("t0", timeout=5)
    assert eng.prefix_cache_stats()["blocks_held"] > 0
    eng.stage_weights(_params2, version=1)
    eng.commit_staged(expected_version=1)
    eng.step()
    assert eng.prefix_cache_stats()["blocks_held"] == 0
    assert eng.prefix_cache_stats()["flushes_total"] == 1
    conv2 = conv + list(first.output_ids) + [11, 12, 13]
    eng.submit(_req("t1", conv2, 8))
    run_until_done(eng)
    got = eng.wait_result("t1", timeout=5)
    fresh = make_engine(params=_params2, prefix_cache=True)
    fresh.submit(_req("f1", conv2, 8))
    run_until_done(fresh)
    assert got.output_ids == fresh.wait_result("f1", timeout=5).output_ids


# -- staged restore through a published snapshot ------------------------------


def test_stage_from_published_snapshot_chunked(tmp_path):
    """The full staged pipeline against a real published orbax snapshot:
    layer-chunked restore onto the engine's tree, manifest validation,
    stage, commit — post-swap stream matches a fresh engine on the new
    weights."""
    snap = str(tmp_path / "v1")
    checkpoint.save_params(_params2, snap)
    checkpoint.write_manifest(_params2, snap, version=1)
    eng = make_engine()
    budget = 60  # survives the commit's ring drain
    eng.submit(_req("q0", [21, 22, 23, 24], budget))
    for _ in range(2):
        eng.step()
    manifest = checkpoint.read_manifest(snap)
    assert manifest is not None and manifest["version"] == 1
    assert checkpoint.validate_manifest(eng.params, manifest) == []
    restored = checkpoint.load_params_staged(
        eng.params, snap, chunk_bytes=16 * 1024
    )
    eng.stage_weights(restored, version=1)
    assert eng.commit_staged(expected_version=1) == 1
    run_until_done(eng)
    out = eng.wait_result("q0", timeout=5)
    assert out.version_end == 1
    split = assert_v0_prefix_v1_tail(
        list(out.output_ids), [21, 22, 23, 24], budget
    )
    assert split < len(out.output_ids)  # the new weights took effect


def test_manifest_mismatch_detected_before_restore(tmp_path):
    snap = str(tmp_path / "v1")
    checkpoint.save_params(_params2, snap)
    checkpoint.write_manifest(_params2, snap, version=1)
    other_cfg = tiny_config(
        vocab_size=32, max_position_embeddings=128, hidden_dim=16
    )
    other = transformer.init_params(other_cfg, jax.random.PRNGKey(7))
    problems = checkpoint.validate_manifest(
        other, checkpoint.read_manifest(snap)
    )
    assert problems, "shape mismatches must be reported"
    assert any("mismatch" in p or "missing" in p for p in problems)


# -- mesh arm (slow: tier-1 keeps the single-chip arms) -----------------------


@pytest.mark.slow
def test_staged_swap_on_tp_mesh_restores_to_serving_shardings(tmp_path):
    """2-chip TP mesh: the staged restore places shards directly at the
    engine's serving shardings (genuinely sharded, never replicated),
    the commit pointer-flips, and the post-swap stream matches a fresh
    mesh engine running the new weights."""
    from areal_tpu.base.topology import MeshSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = MeshSpec(model=2).make_mesh(jax.devices()[:2])
    snap = str(tmp_path / "v1")
    checkpoint.save_params(_params2, snap)
    checkpoint.write_manifest(_params2, snap, version=1)

    def mesh_engine(params):
        return make_engine(params=params, mesh=mesh)

    eng = mesh_engine(_params)
    prompt = [7, 8, 9, 10, 11]
    budget = 60  # survives the commit's ring drain
    eng.submit(_req("q0", prompt, budget))
    for _ in range(2):
        eng.step()
    restored = checkpoint.load_params_staged(
        eng.params, snap, chunk_bytes=16 * 1024
    )
    # restored straight onto the SERVING shardings: the kv/q projections
    # shard over the model axis — never silently replicated
    qw = restored["layers"]["attn"]["q"]["w"]
    assert qw.sharding.shard_shape(qw.shape) != qw.shape
    assert qw.sharding == eng.params["layers"]["attn"]["q"]["w"].sharding
    eng.stage_weights(restored, version=1)
    assert eng.commit_staged(expected_version=1) == 1
    run_until_done(eng)
    got = eng.wait_result("q0", timeout=5)
    assert got.version_end == 1
    fresh = mesh_engine(_params2)
    # the post-swap CONTINUATION must match the fresh mesh engine: replay
    # from the prompt + the v0 prefix the swap interrupted
    split = assert_v0_prefix_v1_tail(list(got.output_ids), prompt, budget)
    fresh.submit(
        _req("f0", prompt + list(got.output_ids)[:split],
             max(len(got.output_ids) - split, 1))
    )
    run_until_done(fresh)
    tail = fresh.wait_result("f0", timeout=5).output_ids
    assert list(got.output_ids)[split:] == tail[: len(got.output_ids) - split]


# -- review hardening: stale stages, idempotent commit retries ----------------


def test_stale_stage_is_dropped_not_parked():
    """A stage that finishes AFTER the round already converged by full
    reload (same or newer version) must not pin a dead tree in memory."""
    eng = make_engine(mode="dense")
    eng.update_weights(_params2, version=2)
    eng.step()
    assert eng.version == 2
    eng.stage_weights(_params, version=1)  # late stale stage
    assert eng.staged_version is None
    eng.stage_weights(_params, version=2)  # same version: also stale
    assert eng.staged_version is None
    eng.stage_weights(_params, version=3)  # genuinely newer: kept
    assert eng.staged_version == 3


def test_full_reload_apply_discards_older_staged_tree():
    """A staged-but-uncommitted tree at or below the version a full
    reload applies is freed at apply time, not at the next round."""
    eng = make_engine(mode="dense")
    eng.stage_weights(_params2, version=1)
    assert eng.staged_version == 1
    eng.update_weights(_params2, version=2)
    eng.step()  # applies the full reload
    assert eng.version == 2
    assert eng.staged_version is None
    with pytest.raises(RuntimeError, match="no staged weights"):
        eng.commit_staged()


def test_commit_retry_after_lost_reply_is_idempotent():
    """A commit whose reply was lost (client timeout) is retried by the
    manager; the retry must ack instead of failing the round (the first
    commit already flipped or queued the version)."""
    from areal_tpu.system.generation_server import GenerationServerWorker
    from areal_tpu.base import logging_

    srv = GenerationServerWorker.__new__(GenerationServerWorker)
    srv.engine = make_engine(mode="dense")
    srv._staging = None
    srv.logger = logging_.getLogger("test-gsw")
    srv.engine.stage_weights(_params2, version=5)
    assert srv._commit_staged({"version": 5}) == 0  # first commit
    # retry BEFORE the engine applied: pending_version matches -> ack
    assert srv.engine.pending_version == 5
    assert srv._commit_staged({"version": 5}) == 0
    srv.engine.step()  # apply
    assert srv.engine.version == 5
    # retry AFTER apply: engine.version matches -> ack
    assert srv._commit_staged({"version": 5}) == 0
    # a DIFFERENT version with nothing staged is still an error
    with pytest.raises(RuntimeError, match="no staged weights"):
        srv._commit_staged({"version": 6})


# -- HBM ledger attribution across the swap lifecycle -------------------------


def test_ledger_attributes_swap_lifecycle_and_close_is_leak_free():
    """The HBM ledger follows the staged-swap state machine: weights
    sized from the live tree, staged_weights non-zero exactly while a
    tree is staged/committed-but-unapplied, and the engine's close()
    leak audit comes back empty after a full swap cycle."""
    from areal_tpu.observability.hbm_ledger import HbmLedger, tree_nbytes

    led = HbmLedger()
    eng = make_engine(mode="dense", hbm_ledger=led)
    snap = led.snapshot()
    assert snap["weights"] == tree_nbytes(eng.params)
    assert snap["kv_pool"] > 0  # the dense KVCache lands under kv_pool
    assert snap["staged_weights"] == 0

    eng.submit(_req("q0", [7, 8, 9], 30))
    for _ in range(2):
        eng.step()
    eng.stage_weights(_params2, version=1)
    staged = led.snapshot()["staged_weights"]
    assert staged == tree_nbytes(_params2)
    # committed-but-unapplied still holds the device tree
    eng.commit_staged(expected_version=1)
    assert led.snapshot()["staged_weights"] == staged
    run_until_done(eng)
    # applied: the staged tree became the live one
    assert led.snapshot()["staged_weights"] == 0
    assert led.snapshot()["weights"] == tree_nbytes(eng.params)

    assert eng.close() == {}  # quiesce audit: no leaked attributions
    assert all(v == 0 for v in led.snapshot().values())
    assert eng.close() == {}  # idempotent


def test_ledger_discard_staged_returns_bytes():
    """discard_staged must hand the staged bytes back — an abandoned
    stage that kept its attribution would read as a leak forever."""
    from areal_tpu.observability.hbm_ledger import HbmLedger

    led = HbmLedger()
    eng = make_engine(mode="dense", hbm_ledger=led)
    eng.stage_weights(_params2, version=1)
    assert led.snapshot()["staged_weights"] > 0
    eng.discard_staged()
    assert led.snapshot()["staged_weights"] == 0
    assert eng.close() == {}


def test_ledger_undiscarded_stage_is_reported_leaked():
    """The audit actually bites: closing with a staged tree still
    resident names staged_weights and its byte count."""
    from areal_tpu.observability.hbm_ledger import HbmLedger, tree_nbytes

    led = HbmLedger()
    eng = make_engine(mode="dense", hbm_ledger=led)
    eng.stage_weights(_params2, version=1)
    leaked = eng.close()
    assert leaked == {"staged_weights": tree_nbytes(_params2)}
    # released regardless: the audit reports, the teardown still cleans
    assert all(v == 0 for v in led.snapshot().values())
