"""Sharded training/inference engine.

Replaces the reference's Megatron backend + pipeline-instruction VM
(reference: realhf/impl/model/backend/megatron.py ``ReaLMegatronEngine``
:410 train_batch with manual micro-batch grad accumulation, finalize_grads
:279; realhf/impl/model/backend/inference.py ``PipelinableInferenceEngine``)
with the JAX SPMD equivalent:

* params/opt-state live as NamedSharding'd global arrays over the model mesh
  (fsdp axis = ZeRO sharding, model axis = tensor parallel) — XLA inserts all
  collectives that Megatron's DDP/DistributedOptimizer did by hand.
* ``train_batch`` packs a SequenceSample once into rows of one length,
  cuts micro-batches as whole rows under the ``MicroBatchSpec``'s budget
  in SLOTS (:func:`plan_layout`), and accumulates grads across
  micro-batches on device; the final apply divides by the global
  denominator, clips, and updates — numerically equal to one big batch.
* loss functions are pure ``(params, cfg, batch) -> (loss_sum, denom, stats)``
  pytrees, so one jitted grad step serves every algorithm interface.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.api.data import (
    MicroBatchSpec,
    SequenceSample,
    SequenceSplitSpec,
)
from areal_tpu.base import logging_
from areal_tpu.engine import batching
from areal_tpu.engine.optimizer import OptimizerConfig, make_optimizer
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import param_pspecs, takes_flash
from areal_tpu.observability.table import (
    TRAIN_BATCH_RECORD_BY_KIND,
    TRAIN_PHASES,
)
from areal_tpu.observability.tracing import PhaseClock, region
from areal_tpu.ops import flash_attention
from areal_tpu.ops import loss as loss_ops

logger = logging_.getLogger("train_engine")

def _fn_key(fn):
    """Compile-cache key for a loss/fwd fn: closure factories set
    ``fn._cache_key`` so fresh closures hit the cache; otherwise id() is used
    (safe: the cache holds a strong reference, so ids are never recycled)."""
    return getattr(fn, "_cache_key", None) or id(fn)


# loss_fn(params, cfg, batch) -> (loss_sum, denom, stats_tree)
LossFn = Callable[
    [Any, TransformerConfig, Dict[str, jax.Array]],
    Tuple[jax.Array, jax.Array, Dict[str, jax.Array]],
]
# fwd_fn(params, cfg, batch) -> pytree of [B, T]-aligned outputs
FwdFn = Callable[[Any, TransformerConfig, Dict[str, jax.Array]], Any]


def plan_layout(
    model_cfg: TransformerConfig,
    seqlens,
    mb_spec: MicroBatchSpec,
    mesh=None,
    row_quantum: int = 1,
    pack: bool = True,
) -> batching.MinibatchPlan:
    """The ``[n, rows, T]`` a minibatch trains at
    (:func:`batching.plan_minibatch`): the rows that cost the model's
    forward pass least, padding counted, by ``flops_counter``'s arithmetic
    (a linear term a slot, an attention term that grows with T^2 a row, or
    with ``T min(T, window)`` in a window layer of a stack stated by
    kind).  Rows grow past the longest sequence only where the model's
    attention takes the flash kernel (:func:`takes_flash`: every attention
    kind of the stack)."""
    from areal_tpu.system import flops_counter

    return batching.plan_minibatch(
        seqlens,
        lambda T: flops_counter.forward_flops(model_cfg, [T]),
        mb_spec.max_tokens_per_mb,
        min_mbs=mb_spec.n_mbs,
        row_quantum=row_quantum,
        pack=pack,
        grow=lambda T: takes_flash(model_cfg, T, mesh),
    )


class TrainEngine:
    """One model on one mesh: sharded params + optional optimizer state."""

    def __init__(
        self,
        model_cfg: TransformerConfig,
        mesh,
        params,
        optimizer_cfg: Optional[OptimizerConfig] = None,
        total_train_steps: int = 1,
        name: str = "",
        pack_sequences: bool = True,
    ):
        self.model_cfg = model_cfg
        self.mesh = mesh
        self.optimizer_cfg = optimizer_cfg
        # sequence packing (FFD segment packing, batching.pack_batch): rows
        # hold multiple segments, so micro-batch [B, T] slots track the real
        # token count instead of n_seqs x bucket(max_len)
        self.pack_sequences = pack_sequences
        # metric label: co-hosted engines (actor + critic on one worker)
        # must not conflate their areal_train_* series
        self.name = name or "model"

        from areal_tpu.parallel import distributed as dist

        self._dist = dist
        self.pipe_size = mesh.shape.get("pipe", 1)
        self.pspecs = param_pspecs(model_cfg, params, pipe=self.pipe_size > 1)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.pspecs
        )
        self.params = dist.tree_put_global(params, self.param_shardings)

        # batch rows shard over data axes; the token axis shards over ``seq``
        # when context parallelism is on (ring attention handles the halo)
        seq_axis = "seq" if mesh.shape.get("seq", 1) > 1 else None
        self.batch_sharding = NamedSharding(
            mesh, P(("data", "fsdp"), seq_axis)
        )
        self.row_sharding = NamedSharding(mesh, P(("data", "fsdp")))
        self.scalar_sharding = NamedSharding(mesh, P())

        if optimizer_cfg is not None:
            self.tx = make_optimizer(optimizer_cfg, total_train_steps)
            # moment shapes/dtypes (incl. mu_dtype/nu_dtype/factored) are
            # fixed HERE: checkpoint save/restore derives its abstract tree
            # from this live state, so the two can never disagree
            self.opt_state = jax.jit(self.tx.init)(self.params)
            from areal_tpu.engine.optimizer import opt_state_bytes

            logger.info(
                "optimizer state: %.2f MB (mu_dtype=%s nu_dtype=%s "
                "factored=%s)",
                opt_state_bytes(self.opt_state) / 2**20,
                optimizer_cfg.mu_dtype,
                optimizer_cfg.nu_dtype,
                optimizer_cfg.factored_second_moment,
            )
        else:
            self.tx = None
            self.opt_state = None

        # compiled-step caches hold a strong reference to the loss/fwd fn so
        # the id()-based key can never be recycled by the GC (round-1 review
        # flagged the bare-id() contract as fragile)
        self._train_step_cache: Dict[Tuple, Tuple[Callable, Callable]] = {}
        #: head products a token of each step program's loss (3 or 4; 0
        #: without a vocabulary head), set when the program is traced
        self._loss_head_products: Dict[Tuple, int] = {}
        self._fwd_step_cache: Dict[int, Tuple[Callable, Callable]] = {}
        self.version = 0
        # what the trainer's thread is doing: a batch's phases are spans
        # in any profiler capture, their self seconds add up here always,
        # and each batch leaves a record (``tracing.PhaseClock``'s laps;
        # ``table.TRAIN_BATCH_RECORD``).  The time BETWEEN two batches,
        # what the interface, the worker and the caller did with the
        # device waiting, is a record's ``t0`` less the one before's ``t1``.
        self._phases = PhaseClock(TRAIN_PHASES, log=f"train.{self.name}")
        self._phases.about = dict(model=self.name)
        self.batches_total = 0

        # observability: step time / token throughput / MFU, scraped off the
        # hosting worker's /metrics endpoint
        from areal_tpu.base.monitor import device_peak_flops
        from areal_tpu.observability import get_registry

        reg = get_registry()
        self._m_step_s = reg.histogram("areal_train_step_seconds")
        self._m_tokens = reg.counter("areal_train_tokens_total")
        self._m_tps = reg.gauge("areal_train_tokens_per_second")
        self._m_mfu = reg.gauge("areal_train_mfu")
        self._m_version = reg.gauge("areal_train_version")
        self._m_pad_frac = reg.gauge("areal_train_padding_frac")
        self._peak_flops = (
            device_peak_flops(mesh.devices.flat[0]) * mesh.devices.size
        )

    # -- helpers ------------------------------------------------------------

    @property
    def dp_size(self) -> int:
        return self.mesh.shape["data"] * self.mesh.shape["fsdp"]

    @property
    def row_quantum(self) -> int:
        """Row-count multiple batches are padded to: the DP shard count,
        times the pipeline micro-batch count when a ``pipe`` axis is live
        (so every pipeline micro-batch stays DP-divisible)."""
        if self.pipe_size > 1:
            m = self.model_cfg.pipe_microbatches or 2 * self.pipe_size
            return self.dp_size * m
        return self.dp_size

    @staticmethod
    def _batch_dict(pb: batching.PaddedBatch) -> Dict[str, np.ndarray]:
        """The device-batch dict: [B, T] arrays, per-row seq_lens, the
        flat segment table, and the extras."""
        return {
            "tokens": pb.tokens,
            "positions": pb.positions,
            "seg_ids": pb.seg_ids,
            "seq_lens": pb.seq_lens,
            "seg_rows": pb.seg_rows,
            "seg_starts": pb.seg_starts,
            "seg_lens": pb.seg_lens,
            **pb.extras,
        }

    def _device_batch(self, pb: batching.PaddedBatch) -> Dict[str, jax.Array]:
        rows = pb.tokens.shape[0]
        out = {}
        for k, v in self._batch_dict(pb).items():
            if v.ndim >= 2:
                sharding = self.batch_sharding
            elif v.shape[0] == rows:
                sharding = self.row_sharding
            else:
                # segment-table / per-segment arrays whose length is not
                # the (dp-divisible) row count: replicate
                sharding = self.scalar_sharding
            out[k] = self._dist.put_global(np.asarray(v), sharding)
        return out

    def _pad(self, sample: SequenceSample, token_key: str) -> batching.PaddedBatch:
        if self.pack_sequences:
            return batching.pack_batch(
                sample,
                token_key=token_key,
                row_multiple=self.row_quantum,
                min_rows=self.row_quantum,
            )
        return batching.pad_batch(
            sample,
            token_key=token_key,
            row_multiple=self.row_quantum,
            min_rows=self.row_quantum,
        )

    # -- training -----------------------------------------------------------

    def _get_train_step(self, loss_fn: LossFn, n_mbs: int):
        """One fused jitted step: grad-accumulate over ``n_mbs`` stacked
        micro-batches (lax.scan), normalize, clip, and apply the optimizer
        update — params/opt_state are donated, and every statistic stays on
        device until the caller's single ``device_get``.

        (Replaces the round-1 per-micro-batch dispatch whose ``float()``
        syncs dominated the step time.)"""
        from areal_tpu.models import transformer

        transformer.set_ambient_mesh(self.mesh)  # for ring attention tracing
        key = (_fn_key(loss_fn), n_mbs)
        if key not in self._train_step_cache:
            grad_of = functools.partial(self._grad_of, loss_fn, key)

            def train_step(params, opt_state, batch):
                if n_mbs == 1:
                    mb = jax.tree.map(lambda x: x[0], batch)
                    grads, loss_sum, denom, stats, _ = grad_of(params, mb)
                else:
                    mb0 = jax.tree.map(lambda x: x[0], batch)
                    carry = grad_of(params, mb0)[:4]

                    def body(carry, mb):
                        g_acc, loss_acc, denom_acc, stats_acc = carry
                        g, ls, dn, st, _ = grad_of(params, mb)
                        with region("areal.optimizer"):
                            g_acc = jax.tree.map(jnp.add, g_acc, g)
                        return (
                            g_acc,
                            loss_acc + ls,
                            denom_acc + dn,
                            jax.tree.map(jnp.add, stats_acc, st),
                        ), None

                    rest = jax.tree.map(lambda x: x[1:], batch)
                    (grads, loss_sum, denom, stats), _ = jax.lax.scan(
                        body, carry, rest
                    )
                with region("areal.optimizer"):
                    grads = jax.tree.map(
                        lambda g: g / jnp.maximum(denom, 1e-8).astype(g.dtype),
                        grads,
                    )
                    gnorm = optax.global_norm(grads)
                    group_norms = self._grad_norms_by_group(grads)
                    updates, opt_state = self.tx.update(
                        grads, opt_state, params
                    )
                    params = optax.apply_updates(params, updates)
                out = {
                    "stats": stats,
                    "loss_sum": loss_sum,
                    "denom": denom,
                    "grad_norm": gnorm,
                    **group_norms,
                }
                return params, opt_state, out

            self._train_step_cache[key] = (
                jax.jit(train_step, donate_argnums=(0, 1)),
                loss_fn,
            )
        return self._train_step_cache[key][0]

    def _grad_of(self, loss_fn: LossFn, key, params, mb):
        """One micro-batch's ``(grads, loss_sum, denom, stats, kept)``:
        the function the step program scans and :meth:`grad_batch` calls.
        ``kept``: what the loss put under ``stats["per_microbatch"]``
        (not a sum over micro-batches: each token's routed experts), which
        the step drops."""

        def scalar_loss(p):
            loss_sum, denom, stats = loss_fn(p, self.model_cfg, mb)
            stats = dict(stats)
            kept = stats.pop("per_microbatch", None)
            return loss_sum, (denom, stats, kept)

        # runs when the program is TRACED: what its loss makes of
        # the head goes on the span of every batch the program steps
        with loss_ops.head_products_traced() as seen:
            (loss_sum, (denom, stats, kept)), grads = jax.value_and_grad(
                scalar_loss, has_aux=True
            )(params)
        self._loss_head_products[key] = max(seen, default=0)
        return grads, loss_sum, denom, stats, kept

    def grad_batch(
        self,
        sample: SequenceSample,
        loss_fn: LossFn,
        mb_spec: MicroBatchSpec,
        token_key: str = "packed_input_ids",
        params=None,
    ):
        """The gradient :meth:`train_batch` would hand its optimizer for
        ``sample`` (same layout, the same micro-batch function, summed and
        divided by the batch's denominator, before clipping) at ``params``
        (the engine's own if None), and nothing applied: ``(grads, out,
        stacked)`` with ``out`` = ``{loss_sum, denom, stats,
        per_microbatch}`` (the last stacked over the micro-batches) and
        ``stacked`` the numpy batch the program saw.  For a check (the
        long train cell reads each token's ROUTING from it, which the step
        program drops, and the gradient from the step program's own first
        moment); it compiles a program of its own."""
        from areal_tpu.models import transformer

        transformer.set_ambient_mesh(self.mesh)
        plan = plan_layout(
            self.model_cfg, sample.seqlens[token_key], mb_spec,
            mesh=self.mesh, row_quantum=self.row_quantum,
            pack=self.pack_sequences,
        )
        stacked, pbs = self._stack_batches(sample, plan, token_key)
        batch = self._upload_stacked(stacked, pbs[0].shape[0])
        n_mbs = next(iter(batch.values())).shape[0]
        key = (_fn_key(loss_fn), n_mbs, "grad_batch")

        def grads_of(params, batch):
            per = [
                self._grad_of(
                    loss_fn, key, params, jax.tree.map(lambda x: x[i], batch)
                )
                for i in range(n_mbs)
            ]
            grads, loss_sum, denom, stats = (
                jax.tree.map(lambda *a: sum(a), *[p[k] for p in per])
                for k in range(4)
            )
            grads = jax.tree.map(
                lambda g: g / jnp.maximum(denom, 1e-8).astype(g.dtype), grads
            )
            kept = None
            if per[0][4] is not None:
                kept = jax.tree.map(lambda *a: jnp.stack(a), *[p[4] for p in per])
            return grads, {
                "loss_sum": loss_sum, "denom": denom, "stats": stats,
                "per_microbatch": kept,
            }

        grads, out = jax.jit(grads_of)(
            self.params if params is None else params, batch
        )
        return grads, jax.device_get(out), stacked

    def _grad_norms_by_group(self, grads) -> Dict[str, Any]:
        """``{"grad_norms": {group: norm}}`` of a stack stated by kind
        (``hybrid.grad_group``: attention by kind, gates, router, held
        experts, shared expert, dense MLP, embedding, head, norms), before
        clipping; nothing for the dense stack, whose step program stays
        what it was."""
        if not self.model_cfg.is_hybrid:
            return {}
        from areal_tpu.models import hybrid

        return {"grad_norms": hybrid.grad_norms_by_group(grads)}

    def _stack_batches(
        self,
        sample: SequenceSample,
        plan: batching.MinibatchPlan,
        token_key: str,
    ):
        """Lay the plan's micro-batches out at its [rows, T] and stack to
        [n, rows, T], in numpy: ``(stacked, padded batches)``."""
        mbs = SequenceSample.reorder(
            sample, [i for g in plan.groups for i in g]
        ).split_with_spec(SequenceSplitSpec(sizes=list(map(len, plan.groups))))
        if self.pack_sequences:
            seg_cap = batching.next_pow2(
                max(sum(map(len, b)) for b in plan.bins)
            )
            pbs = [
                batching.pack_batch(
                    mb,
                    token_key=token_key,
                    fixed_rows=plan.rows,
                    fixed_len=plan.row_len,
                    fixed_segs=seg_cap,
                    bins=b,
                )
                for mb, b in zip(mbs, plan.bins)
            ]
        else:
            pbs = [
                batching.pad_batch(
                    mb,
                    token_key=token_key,
                    fixed_rows=plan.rows,
                    fixed_len=plan.row_len,
                )
                for mb in mbs
            ]
        batches = [self._batch_dict(pb) for pb in pbs]
        # the all-zero micro-batches of the bucketed count: seg_ids 0 ->
        # zero loss, zero denom, zero grads; seg_lens 0 -> every segment
        # masked out of per-segment gathers
        for _ in range(plan.n_stacked - len(batches)):
            batches.append(
                {k: np.zeros_like(v) for k, v in batches[0].items()}
            )
        stacked = {
            k: np.stack([b[k] for b in batches]) for k in batches[0]
        }
        return stacked, pbs

    def _upload_stacked(self, stacked, rows: int):
        """The stacked micro-batches on the devices."""
        out = {}
        for k, v in stacked.items():
            if v.ndim >= 3:
                spec = self.batch_sharding.spec
            elif v.shape[1] == rows:
                spec = self.row_sharding.spec
            else:  # segment table / per-segment scalars: replicate
                spec = P()
            sharding = NamedSharding(self.mesh, P(None, *spec))
            out[k] = self._dist.put_global(v, sharding)
        return out

    def train_batch(
        self,
        sample: SequenceSample,
        loss_fn: LossFn,
        mb_spec: MicroBatchSpec,
        token_key: str = "packed_input_ids",
    ) -> Dict[str, float]:
        """Micro-batched, grad-accumulated train step over ``sample``."""
        import time

        assert self.tx is not None, "engine built without an optimizer"
        tik = time.perf_counter()
        clock = self._phases
        with clock.phase("areal.train.batch") as span:
            with clock.phase("areal.train.pack"):
                plan = plan_layout(
                    self.model_cfg,
                    sample.seqlens[token_key],
                    mb_spec,
                    mesh=self.mesh,
                    row_quantum=self.row_quantum,
                    pack=self.pack_sequences,
                )
                stacked, pbs = self._stack_batches(sample, plan, token_key)
            rows, row_len = pbs[0].shape
            with clock.phase("areal.train.upload"):
                batch = self._upload_stacked(stacked, rows)
            n_mbs = next(iter(batch.values())).shape[0]  # bucketed count
            # padding waste of this step's device layout: stacked
            # [n, B, T] slots (INCLUDING all-zero bucketing micro-batches
            # — they burn the same compute) vs real tokens
            slots = n_mbs * pbs[0].padded_slots
            real_tokens = sum(
                int(l)
                for per_id in sample.seqlens[token_key]
                for l in per_id
            )
            self.last_padded_slots = slots
            self.last_padding_frac = 1.0 - real_tokens / max(slots, 1)
            self.padded_slots_total += slots
            self.real_tokens_total += real_tokens
            self._m_pad_frac.set(self.last_padding_frac, model=self.name)
            # the block pairs the flash kernels run on this layout, of
            # those under the diagonal (the kernels' own rule, on the host)
            blocks_run, blocks_causal = flash_attention.blocks_run(
                stacked["seg_ids"].reshape(-1, row_len)
            )
            self.attn_blocks_run_total += blocks_run
            self.attn_blocks_causal_total += blocks_causal
            counts = dict(
                real_tokens=real_tokens, padded_slots=slots, n_mbs=n_mbs,
                rows=rows, row_len=row_len, attn_blocks_run=blocks_run,
                attn_blocks_causal=blocks_causal,
            )
            if self.model_cfg.is_hybrid and self.model_cfg.n_window_layers:
                # a window layer's pairs, of the same triangle
                counts["attn_window_blocks_run"], _ = flash_attention.blocks_run(
                    stacked["seg_ids"].reshape(-1, row_len),
                    window=self.model_cfg.sliding_window,
                )
            span.set_metadata(**counts)
            step = self._get_train_step(loss_fn, n_mbs)
            with clock.phase("areal.train.dispatch"):
                self.params, self.opt_state, out = step(
                    self.params, self.opt_state, batch
                )
            span.set_metadata(
                loss_head_products=self._loss_head_products[
                    (_fn_key(loss_fn), n_mbs)
                ]
            )
            self.version += 1
            self.batches_total += 1
            clock.note(
                batch=self.batches_total, version=self.version, **counts
            )
            with clock.phase("areal.train.sync"):
                out = jax.device_get(out)  # ONE host sync per train step
            if "grad_norms" in out:
                # a stack stated by kind: the batch's gradient by group and
                # its expert layers' counts go on its record too
                clock.note(
                    grad_norms={
                        k: float(v) for k, v in out["grad_norms"].items()
                    },
                    grad_norm=float(out["grad_norm"]),
                    **{
                        k[: -len("_sum")]: float(v)
                        for k, v in out["stats"].items()
                        if k[: -len("_sum")] in TRAIN_BATCH_RECORD_BY_KIND
                    },
                )
        elapsed = time.perf_counter() - tik
        denom_f = float(out["denom"])
        self._record_step_metrics(sample, token_key, elapsed, denom_f)
        host_stats: Dict[str, float] = {}
        # jax.tree.leaves_with_path only exists from jax 0.5; tree_util's
        # spelling works on every version this repo supports
        for k, v in jax.tree_util.tree_leaves_with_path(out["stats"]):
            name = "/".join(
                p.key if hasattr(p, "key") else str(p) for p in k
            )
            host_stats[name] = float(v)
        for group, norm in out.get("grad_norms", {}).items():
            host_stats[f"grad_norm/{group}"] = float(norm)
        host_stats.update(
            loss=float(out["loss_sum"]) / max(denom_f, 1e-8),
            grad_norm=float(out["grad_norm"]),
            n_tokens=denom_f,
            n_mbs=len(pbs),
            tokens_per_sec=self.last_tokens_per_sec,
        )
        if self.last_mfu > 0:
            host_stats["mfu"] = self.last_mfu
        return host_stats

    #: last step's throughput/MFU/padding waste (also exported as gauges)
    last_tokens_per_sec: float = 0.0
    last_mfu: float = 0.0
    last_padding_frac: float = 0.0
    last_padded_slots: int = 0
    #: cumulative over every train_batch call (every minibatch of a step,
    #: not its last): padding share = 1 - real_tokens_total /
    #: padded_slots_total
    padded_slots_total: int = 0
    real_tokens_total: int = 0
    #: flash-attention block pairs run / under the diagonal, cumulative
    #: (``ops/flash_attention.blocks_run`` of every layout built)
    attn_blocks_run_total: int = 0
    attn_blocks_causal_total: int = 0

    def _record_step_metrics(
        self,
        sample: SequenceSample,
        token_key: str,
        elapsed: float,
        n_tokens: float,
    ):
        """Step time, token throughput, and (on hardware with a known peak)
        MFU — the train-side half of the observability plane."""
        self._m_step_s.observe(elapsed, model=self.name)
        if n_tokens > 0:
            self._m_tokens.inc(n_tokens, model=self.name)
        self.last_tokens_per_sec = n_tokens / max(elapsed, 1e-9)
        self._m_tps.set(self.last_tokens_per_sec, model=self.name)
        self._m_version.set(self.version, model=self.name)
        self.last_mfu = 0.0
        if self._peak_flops > 0:
            try:
                from areal_tpu.system import flops_counter

                lens = [
                    int(l)
                    for per_id in sample.seqlens[token_key]
                    for l in per_id
                ]
                fl = flops_counter.train_flops(self.model_cfg, lens)
                self.last_mfu = fl / max(elapsed, 1e-9) / self._peak_flops
                self._m_mfu.set(self.last_mfu, model=self.name)
            except Exception:  # noqa: BLE001 - accounting never kills a step
                pass

    # -- inference ----------------------------------------------------------

    def _get_fwd_step(self, fwd_fn: FwdFn):
        from areal_tpu.models import transformer

        transformer.set_ambient_mesh(self.mesh)
        key = _fn_key(fwd_fn)
        if key not in self._fwd_step_cache:
            self._fwd_step_cache[key] = (
                jax.jit(
                    lambda params, batch: fwd_fn(params, self.model_cfg, batch)
                ),
                fwd_fn,
            )
        return self._fwd_step_cache[key][0]

    def forward_batch(
        self,
        sample: SequenceSample,
        fwd_fn: FwdFn,
        mb_spec: MicroBatchSpec,
        token_key: str = "packed_input_ids",
        output_shift: int = 0,
    ) -> np.ndarray:
        """Run ``fwd_fn`` over micro-batches; returns the packed 1-D concat of
        per-token outputs in the ORIGINAL sequence order.

        ``output_shift=1`` for transition-aligned outputs (length L-1)."""
        mbs, fwd_idx, bwd_idx = sample.split(mb_spec)
        step = self._get_fwd_step(fwd_fn)
        packed_parts = []
        # dispatch micro-batch N+1 BEFORE gathering micro-batch N: jax
        # dispatch is async, so mb N's fetch RTT (PCIe) rides under
        # mb N+1's device time instead of serializing the chain (the
        # ref-logprob and critic passes were host-sync chains before)
        pending = None  # (device output, PaddedBatch) of the previous mb
        for mb in mbs:
            pb = self._pad(mb, token_key)
            batch = self._device_batch(pb)
            out_dev = step(self.params, batch)
            if pending is not None:
                prev_out, prev_pb = pending
                packed_parts.append(
                    batching.unpack_per_token(
                        self._dist.host_gather(prev_out),
                        prev_pb,
                        shift=output_shift,
                    )
                )
            pending = (out_dev, pb)
        prev_out, prev_pb = pending
        packed_parts.append(
            batching.unpack_per_token(
                self._dist.host_gather(prev_out), prev_pb, shift=output_shift
            )
        )
        packed = np.concatenate(packed_parts, axis=0)
        expected = [
            [l - output_shift for l in ls]
            for ls in sample.seqlens[token_key]
        ]
        return SequenceSample.reorder_output(
            packed, expected, fwd_idx, bwd_idx
        )

    # -- weights ------------------------------------------------------------

    def get_host_params(self):
        """Gather full params to host numpy (for HF export / weight sync);
        multi-host safe (process_allgather under the hood when sharded
        across processes)."""
        return self._dist.tree_host_gather(self.params)

    def set_params(self, params):
        self.params = self._dist.tree_put_global(params, self.param_shardings)

    def save_hf(self, path: str, family: str, tokenizer=None):
        from areal_tpu.models.hf import save_hf_model

        save_hf_model(
            path, family, self.model_cfg, self.get_host_params(), tokenizer
        )

    def save_train_state(self, path: str):
        """Sharded {params, opt_state, version} checkpoint (per-host shard
        writes via orbax; replaces the round-1 host-gathered pickle)."""
        from areal_tpu.engine import checkpoint

        checkpoint.save_train_state(self, path)

    def load_train_state(self, path: str) -> bool:
        from areal_tpu.engine import checkpoint

        return checkpoint.load_train_state(self, path)

