#!/usr/bin/env python3
"""Drive the system's main path once on a real TPU, through the entry
points a user would call, at published Qwen2.5 widths.

    python chip_smoke.py             # one chip: `serve`, then `async_ppo`
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

One process, one (or four) chip(s): every worker is a thread of this
process (``apps.local_runner``), and nothing it starts needs a chip.  It
FAILS unless ``jax.devices()[0].platform == "tpu"`` — it never sets or
clears a platform, retries backend init, or catches a phase's failure.

Each phase is a function that returns its report (printed as one JSON
line); tests/test_chip_smoke.py calls them on the CPU with a tiny config.
What only a chip can show is asserted in ``main()``.  The LAST line of
stdout is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.

Weights are random, made from ``--seed``; so are the tokenizer and the
math dataset (the chip machine has no network and no checkpoint).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import random
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: published widths (Qwen/Qwen2.5-1.5B and Qwen/Qwen2.5-7B config.json)
QWEN25_1P5B = dict(
    n_layers=28, hidden_dim=1536, n_q_heads=12, n_kv_heads=2, head_dim=128,
    intermediate_dim=8960, vocab_size=151936, max_position_embeddings=32768,
    use_attention_bias=True, tied_embedding=True, dtype="bfloat16",
)
QWEN25_7B = dict(
    n_layers=28, hidden_dim=3584, n_q_heads=28, n_kv_heads=4, head_dim=128,
    intermediate_dim=18944, vocab_size=152064, max_position_embeddings=32768,
    use_attention_bias=True, tied_embedding=False, dtype="bfloat16",
)

#: |server logprob - reference logprob| bounds for the `serve` phase.  The
#: server runs bf16 weights AND bf16 activations through the paged kernel
#: and a KV cache; the reference runs the same bf16-rounded weights with
#: float32 activations, dense attention, no cache, "highest" matmul
#: precision.  What separates them is bf16 rounding of activations over
#: 28 layers landing on near-uniform logits (random weights, mean logp
#: -11.93 = -ln 151936): measured on a v5e at 0.0023 max / 0.0005 mean.
#: The bounds sit ~10x above that; a wrong page, mask, position or weight
#: shows up as errors of order 0.1-1.
SERVE_LOGP_MAX_ABS = 0.02
SERVE_LOGP_MEAN_ABS = 0.005

#: TP=2 engine vs one-chip engine (--chips 4), same bf16 weights, greedy:
#: the two differ only in the order of bf16 partial sums across shards.
TP_LOGP_MAX_ABS = 0.1
#: FSDP-2 first train-step loss vs a one-chip forward of the same batch
#: (same fp32 masters, bf16 compute, different reduction orders)
FSDP_LOSS_REL = 2e-2

_WORDS = (
    "the quick brown fox jumps over lazy dog and then runs away from big "
    "scary bear in forest during sunny day while birds sing beautiful songs "
    "under blue sky with white clouds floating gently"
).split()


def emit(report: dict) -> dict:
    print(json.dumps(report), flush=True)
    return report


# ---------------------------------------------------------------------------
# seeded fixtures (the way tests/fixtures.py builds them)
# ---------------------------------------------------------------------------


def make_fixtures(workdir: str, seed: int, n_rows: int = 24):
    """A math ``jsonl`` and a 200-word WordPiece tokenizer, both from the
    seed.  Returns (dataset_path, tokenizer_path)."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordPiece
    from tokenizers.pre_tokenizers import Whitespace
    from tokenizers.trainers import WordPieceTrainer
    from transformers import PreTrainedTokenizerFast

    rnd = random.Random(seed)

    def sentence(n):
        return " ".join(rnd.choices(_WORDS, k=n)) + "\n"

    rows = []
    for i in range(n_rows):
        qid = f"q{seed}-{i}"
        rows.append(
            dict(
                id=qid,
                query_id=qid,
                prompt=sentence(rnd.randint(4, 24)),
                solutions=["\\boxed{42}"],
                answer=sentence(rnd.randint(1, 8)),
                task="math",
            )
        )
    os.makedirs(workdir, exist_ok=True)
    dataset_path = os.path.join(workdir, "math.jsonl")
    with open(dataset_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    tok = Tokenizer(WordPiece(unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    tok.train_from_iterator(
        [r["prompt"] + r["answer"] for r in rows],
        WordPieceTrainer(
            vocab_size=200, special_tokens=["[UNK]", "[PAD]", "[EOS]"]
        ),
    )
    tokenizer_path = os.path.join(workdir, "tokenizer")
    PreTrainedTokenizerFast(
        tokenizer_object=tok,
        unk_token="[UNK]",
        pad_token="[PAD]",
        eos_token="[EOS]",
    ).save_pretrained(tokenizer_path)
    return dataset_path, tokenizer_path


def point_roots_at(workdir: str):
    """Logs, published weights and caches all live under ``workdir``
    (inside the checkout): nothing is written around it."""
    for var, sub in (
        ("AREAL_LOG_ROOT", "logs"),
        ("AREAL_SAVE_ROOT", "save"),
        ("AREAL_CACHE_ROOT", "cache"),
    ):
        os.environ[var] = os.path.join(workdir, sub)


def hbm_peak_gb():
    """Peak bytes in use per local device, in GB (None off-TPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() if d.platform == "tpu" else None
        out.append(
            round(stats["peak_bytes_in_use"] / 1e9, 3) if stats else None
        )
    return out


class CompileClock:
    """Seconds jax spent compiling (or fetching from the persistent cache),
    summed from its own monitoring events, plus cache hits/misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            with self._lock:
                self.seconds += secs

    def _on_event(self, event, **_):
        with self._lock:
            if event.endswith("/cache_hits"):
                self.cache_hits += 1
            elif event.endswith("/cache_misses"):
                self.cache_misses += 1

    def snapshot(self):
        with self._lock:
            return (self.seconds, self.cache_hits, self.cache_misses)

    def since(self, snap):
        s, h, m = self.snapshot()
        return {
            "compile_seconds": round(s - snap[0], 2),
            "cache_hits": h - snap[1],
            "cache_misses": m - snap[2],
        }


def _actor_engine(model_worker):
    return next(
        m.engine
        for m in model_worker._models.values()
        if m.name.role == "actor"
    )


def fresh_workdir(workdir: str):
    """Each phase starts from an empty work directory: a stale published
    snapshot would win the publisher's keep-last-2 GC against new ones."""
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    point_roots_at(workdir)


def _model_abs(model_cfg: dict, seed: int):
    from areal_tpu.api.config import ModelAbstraction

    return ModelAbstraction("random", {"config": dict(model_cfg), "seed": seed})


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def phase_serve(
    model_cfg: dict,
    seed: int,
    workdir: str,
    *,
    prompt_lens=(1500, 700, 120, 30),
    group_prompt_len=300,
    group_size=4,
    max_new_tokens=320,
    kv_cache_len=4096,
    max_batch=16,
    page_size=1024,
    prefill_chunk_tokens=1024,
    chunk_size=64,
    n_reference=3,
    clock: CompileClock = None,
) -> dict:
    """A GenerationServerWorker answering requests that reach it the way a
    rollout worker's do (GserverManager schedules, GenServerClient
    generates), then — outside any timing — the server's own
    log-probabilities against a plain full forward."""
    import jax
    import numpy as np

    from areal_tpu.api import model_api
    from areal_tpu.api.system_api import GenServerConfig, GserverManagerConfig
    from areal_tpu.base import constants, name_resolve, names
    from areal_tpu.models import paged
    from areal_tpu.system.generation_server import GenerationServerWorker
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerClient,
    )
    from areal_tpu.system.partial_rollout import PartialRolloutManager

    t_phase = time.perf_counter()
    snap = clock.snapshot() if clock else None
    fresh_workdir(workdir)
    _, tokenizer_path = make_fixtures(workdir, seed)
    expr, trial = "chip-smoke", f"serve-{seed}"
    constants.set_experiment_trial_names(expr, trial)

    server = GenerationServerWorker()
    errors = []

    def run(worker, cfg):
        try:
            worker.run(cfg)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    st = threading.Thread(
        target=run,
        args=(
            server,
            GenServerConfig(
                worker_name="gen_server_0",
                model=_model_abs(model_cfg, seed),
                tokenizer_path=tokenizer_path,
                max_concurrent_batch=max_batch,
                kv_cache_len=kv_cache_len,
                chunk_size=chunk_size,
                cache_mode="auto",
                page_size=page_size,
                prefill_chunk_tokens=prefill_chunk_tokens,
                device_idx=0,
            ),
        ),
        daemon=True,
        name="gen_server_0",
    )
    st.start()
    manager = GserverManager()
    mt = threading.Thread(
        target=run,
        args=(manager, GserverManagerConfig(n_servers=1)),
        daemon=True,
        name="gserver_manager",
    )

    def wait_key(key, what):
        deadline = time.monotonic() + 900
        while time.monotonic() < deadline:
            if errors:
                raise RuntimeError(f"{what} failed to start") from errors[0]
            try:
                return name_resolve.wait(key, timeout=1)
            except TimeoutError:
                continue
        raise TimeoutError(f"{what} did not register")

    wait_key(names.gen_server(expr, trial, "gen_server_0"), "gen server")
    t_ready = time.perf_counter()
    mt.start()
    wait_key(names.gen_server_manager(expr, trial), "gserver manager")

    engine = server.engine
    rng = np.random.default_rng(seed)
    vocab = model_cfg["vocab_size"]
    prompts = [
        rng.integers(3, vocab, (n,)).tolist() for n in prompt_lens
    ]
    group_prompt = rng.integers(3, vocab, (group_prompt_len,)).tolist()
    gconfig = model_api.GenerationHyperparameters(
        max_new_tokens=max_new_tokens, temperature=1.0
    )
    client = GserverManagerClient(expr, trial)
    prm = PartialRolloutManager(client, gconfig)

    async def drive():
        jobs = [
            prm.generate_group(f"solo{i}", p, 1)
            for i, p in enumerate(prompts)
        ]
        jobs.append(prm.generate_group("group", group_prompt, group_size))
        return await asyncio.gather(*jobs)

    t0 = time.perf_counter()
    try:
        bundles = asyncio.run(drive())
    finally:
        prm.close()
        client.close()
    gen_seconds = time.perf_counter() - t0
    if errors:
        raise RuntimeError("a serving worker failed") from errors[0]

    seqs, logps, plens = [], [], []
    for b in bundles:
        for seq, lp in zip(b.seqs, b.logprobs):
            seqs.append(list(seq))
            logps.append(list(lp))
            plens.append(len(b.prompt_ids))
    n_new = [len(s) - p for s, p in zip(seqs, plens)]

    report = {
        "phase": "serve",
        "layers": model_cfg["n_layers"],
        "hidden_dim": model_cfg["hidden_dim"],
        "seconds_to_ready": round(t_ready - t_phase, 2),
        "generate_seconds": round(gen_seconds, 2),
        "requests": len(seqs),
        "prompt_lens": plens,
        "tokens_generated": int(sum(n_new)),
        "paged": bool(engine.paged),
        "use_paged_kernel": bool(getattr(engine, "_use_paged_kernel", False)),
        "kernel_interpret": bool(paged.kernel_interpret()),
        "prefill_chunk_tokens": prefill_chunk_tokens,
        "weight_dtype": str(jax.tree.leaves(engine.params)[0].dtype),
    }

    # -- outside any timing: the server's logprobs vs a plain forward -----
    # the longest prompt (chunked prefill), a group member (shared prompt)
    # and the shortest prompt
    order = sorted(range(len(seqs)), key=lambda i: -plens[i])
    picks = [order[0], len(prompts), order[-1]][:n_reference]
    report["reference"] = reference_check(
        engine.cfg, engine.params, [seqs[i] for i in picks],
        [logps[i] for i in picks], [plens[i] for i in picks],
    )

    server.exit()
    manager.exit()
    st.join(timeout=60)
    mt.join(timeout=60)
    if errors:
        raise RuntimeError("a serving worker failed") from errors[0]
    name_resolve.reset()
    del server, manager, engine
    gc.collect()
    report["seconds"] = round(time.perf_counter() - t_phase, 2)
    report["hbm_peak_gb"] = hbm_peak_gb()
    if clock:
        report.update(clock.since(snap))
    return report


def reference_check(cfg, params, seqs, server_logps, prompt_lens) -> dict:
    """Log-probabilities of each sequence's generated tokens from a plain
    full forward (float32 activations over the engine's own weights, dense
    ``reference_attention``, no cache, "highest" matmul precision),
    compared with what the server returned for them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import transformer
    from areal_tpu.ops import flash_attention as fa

    ref_cfg = dataclasses.replace(cfg, dtype="float32")

    @jax.jit
    def token_logps(params, tokens):
        T = tokens.shape[1]
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        seg = jnp.ones((1, T), jnp.int32)
        logits = transformer.forward(params, ref_cfg, tokens, positions, seg)
        lp = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp, tokens[0, 1:, None], axis=-1)[:, 0]

    rows = []
    for seq, got, plen in zip(seqs, server_logps, prompt_lens):
        # right-pad to the next length the flash kernel does NOT take, so
        # this forward stays on the dense reference attention (causal:
        # padding after the sequence changes nothing before it)
        T = len(seq)
        while fa.supported(T, T, None):
            T += 1
        tokens = jnp.asarray([list(seq) + [0] * (T - len(seq))], jnp.int32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(token_logps(params, tokens))[: len(seq) - 1]
        # logprobs are transition-aligned over the whole sequence
        # (len(seq) - 1); the generated tokens are the tail
        got = np.asarray(got, np.float32)[plen - 1 :]
        want = ref[plen - 1 :]
        assert got.shape == want.shape, (got.shape, want.shape)
        diff = np.abs(got - want)
        rows.append(
            {
                "prompt_len": plen,
                "new_tokens": int(len(seq) - plen),
                "max_abs_diff": float(f"{diff.max():.3g}"),
                "mean_abs_diff": float(f"{diff.mean():.3g}"),
                "mean_logp": round(float(want.mean()), 4),
            }
        )
    return {
        "sequences": rows,
        "max_abs_diff": max(r["max_abs_diff"] for r in rows),
        "mean_abs_diff": max(r["mean_abs_diff"] for r in rows),
        "tolerance": {
            "max_abs": SERVE_LOGP_MAX_ABS, "mean_abs": SERVE_LOGP_MEAN_ABS
        },
    }


# ---------------------------------------------------------------------------
# phase: async_ppo
# ---------------------------------------------------------------------------


def param_count(model_cfg: dict, n_layers: int) -> int:
    D, F = model_cfg["hidden_dim"], model_cfg["intermediate_dim"]
    qd = model_cfg["n_q_heads"] * model_cfg["head_dim"]
    kvd = model_cfg["n_kv_heads"] * model_cfg["head_dim"]
    per_layer = 2 * D * qd + 2 * D * kvd + 3 * D * F + qd + 2 * kvd + 2 * D
    embed = model_cfg["vocab_size"] * D
    return embed * (1 if model_cfg["tied_embedding"] else 2) + (
        n_layers * per_layer
    ) + D


def fit_layers(
    model_cfg: dict,
    hbm_bytes: int,
    *,
    trainer_chips: int = 1,
    server_shares_chip: bool = True,
    pool_tokens: int = 16 * 4096,
    reserve: float = 3.0e9,
    budget_frac: float = 0.85,
) -> dict:
    """Largest depth whose async-PPO footprint fits one chip's HBM, with
    the arithmetic printed.  Per parameter: float32 master 4 B + Adam
    m, v 8 B + float32 gradient 4 B on the trainer (sharded over its
    chips); where the server shares the chip, its bf16 copy 2 B + the
    staged copy a weight swap holds beside it 2 B + the bf16/int8
    snapshots a publish casts on device 3 B.  Beside the weights: the KV
    pool, and a fixed reserve for activations, the chunked 152k-vocab
    loss and XLA's own scratch.  Widths are never cut."""
    trainer_b = 16.0 / trainer_chips
    server_b = 7.0 if server_shares_chip else 3.0 / trainer_chips
    kv_per_tok_layer = (
        2 * model_cfg["n_kv_heads"] * model_cfg["head_dim"] * 2
    )
    budget = budget_frac * hbm_bytes
    best = 0
    for L in range(1, model_cfg["n_layers"] + 1):
        need = (
            param_count(model_cfg, L) * (trainer_b + server_b)
            + (pool_tokens * kv_per_tok_layer * L if server_shares_chip else 0)
            + reserve
        )
        if need > budget:
            break
        best = L
    if best == 0:
        raise RuntimeError(
            f"not even one layer fits: {param_count(model_cfg, 1)} params x "
            f"{trainer_b + server_b} B + {reserve:.1e} B > {budget:.3e} B"
        )
    return {
        "layers": best,
        "of": model_cfg["n_layers"],
        "params": param_count(model_cfg, best),
        "bytes_per_param": trainer_b + server_b,
        "reserve_bytes": reserve,
        "budget_bytes": int(budget),
        "why": (
            f"{trainer_b:g} B/param trainer (fp32 master+Adam+grad over "
            f"{trainer_chips} chip(s)) + {server_b:g} B/param server-side "
            f"copies + KV pool + {reserve / 1e9:g} GB reserve must fit "
            f"{budget_frac:.0%} of {hbm_bytes / 1e9:.2f} GB HBM"
        ),
    }


def phase_async_ppo(
    model_cfg: dict,
    seed: int,
    workdir: str,
    *,
    n_layers: int,
    train_steps: int = 3,
    max_new_tokens: int = 1024,
    train_bs_n_seqs: int = 8,
    group_size: int = 4,
    max_tokens_per_mb: int = 4096,
    gen_kv_cache_len: int = 2048,
    gen_max_batch: int = 16,
    page_size: int = 1024,
    prefill_chunk_tokens: int = 1024,
    gen_chunk_size: int = 64,
    allocation_mode: str = "",
    timeout: float = 1500.0,
    adopt_timeout: float = 240.0,
    trial: str = "async",
    clock: CompileClock = None,
    inspect=None,
) -> dict:
    """What training/main_async_ppo.py does: AsyncPPOMathExperiment ->
    initial_setup() -> run_experiment_local, with a ``random`` actor.
    ``inspect(workers)`` runs while the engines are still live."""
    import numpy as np

    from areal_tpu.api.config import DatasetAbstraction
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.api.system_api import ExperimentSaveEvalControl
    from areal_tpu.apps.local_runner import register_impls, run_experiment_local
    from areal_tpu.base import constants, name_resolve
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.experiments.async_ppo_exp import AsyncPPOMathExperiment
    from areal_tpu.experiments.ppo_math_exp import PPOHyperparameters
    from areal_tpu.models import transformer
    from areal_tpu.observability import get_registry
    from areal_tpu.system.generation_server import GenerationServerWorker
    from areal_tpu.system.model_worker import ModelWorker

    t_phase = time.perf_counter()
    snap = clock.snapshot() if clock else None
    fresh_workdir(workdir)
    dataset_path, tokenizer_path = make_fixtures(workdir, seed)
    cfg_dict = dict(model_cfg, n_layers=n_layers, remat=True)
    warned_before = set(transformer._warned_dense)

    register_impls()
    exp = AsyncPPOMathExperiment(
        experiment_name="chip-smoke",
        trial_name=f"{trial}-{seed}",
        seed=seed,
        n_model_workers=1,
        mesh_spec=MeshSpec(),  # one device unless allocation_mode says
        allocation_mode=allocation_mode,
        exp_ctrl=ExperimentSaveEvalControl(
            total_train_epochs=1000, benchmark_steps=train_steps
        ),
        tokenizer_path=tokenizer_path,
        actor=_model_abs(cfg_dict, seed),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_path": dataset_path, "max_length": 256},
        ),
        train_bs_n_seqs=train_bs_n_seqs,
        group_size=group_size,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb),
        actor_optimizer=OptimizerConfig(lr=1e-5),
        ppo=PPOHyperparameters(
            gen=GenerationHyperparameters(
                max_new_tokens=max_new_tokens,
                min_new_tokens=max_new_tokens,
                temperature=1.0,
            ),
            ppo_n_minibatches=1,
            kl_ctl=0.0,
            disable_value=True,
            use_decoupled_loss=True,
            behav_imp_weight_cap=5.0,
            # a random model never boxes the right answer, so every reward
            # is the same 0: a reward bias and un-normalized advantages
            # keep the loss and its gradient non-zero (otherwise every
            # advantage, the loss and the update would be exactly 0)
            reward_output_bias=0.5,
            adv_norm=False,
        ),
        n_rollout_workers=1,
        n_gen_servers=1,
        gen_device_start=None if allocation_mode else 0,
        # the staleness gate is left wide open: a handful of steps never
        # reaches it, so the rollout side keeps rows in flight through
        # every weight update (what this phase has to show)
        max_head_offpolicyness=64,
        max_concurrent_rollouts=max(1, gen_max_batch // group_size),
        gen_kv_cache_len=gen_kv_cache_len,
        gen_max_concurrent_batch=gen_max_batch,
        gen_chunk_size=gen_chunk_size,
        gen_page_size=page_size,
        gen_prefill_chunk_tokens=prefill_chunk_tokens,
    )
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)

    live = {}

    def before_exit(workers):
        gen = next(w for w in workers if isinstance(w, GenerationServerWorker))
        mw = next(w for w in workers if isinstance(w, ModelWorker))
        eng = gen.engine
        trainer = _actor_engine(mw)
        # training is over; the rollout side is still generating.  Give the
        # fleet a bounded time to adopt the last published weights (the
        # first swap compiles the in-flight rows' refill shapes)
        deadline = time.monotonic() + adopt_timeout
        while (
            eng.version < trainer.version or eng.swap_applying
        ) and time.monotonic() < deadline:
            time.sleep(0.5)
        live.update(
            areal_train_mfu=round(
                float(
                    get_registry()
                    .gauge("areal_train_mfu")
                    .value(model=trainer.name)
                ),
                5,
            ),
            server_version=int(eng.version),
            swaps_total=int(eng.swaps_total),
            swaps_staged=int(eng.swaps_staged_total),
            swap_recomputed_rows=int(eng.swap_recomputed_rows_total),
            swap_pause_seconds=round(float(eng.swap_pause_s), 3),
            server_tokens_generated=int(eng.gen_tokens_total),
            server_paged=bool(eng.paged),
            server_use_paged_kernel=bool(
                getattr(eng, "_use_paged_kernel", False)
            ),
            trainer_version=int(trainer.version),
            trainer_mesh={
                k: int(v) for k, v in trainer.mesh.shape.items() if v > 1
            },
        )
        if inspect is not None:
            live["inspect"] = inspect(workers)

    master = run_experiment_local(
        cfg, timeout=timeout, before_exit=before_exit
    )
    name_resolve.reset()

    hist = master.stats_history
    losses = [float(s["actor_train/loss"]) for s in hist]
    report = {
        "phase": "async_ppo",
        "layers": n_layers,
        "hidden_dim": model_cfg["hidden_dim"],
        "allocation_mode": allocation_mode or "one device",
        "train_steps": len(hist),
        "losses": [round(x, 6) for x in losses],
        "grad_norms": [
            round(float(s.get("actor_train/grad_norm", float("nan"))), 6)
            for s in hist
        ],
        "tokens_per_step": [
            int(s.get("actor_train/n_tokens", 0)) for s in hist
        ],
        "train_mfu": [
            round(float(s.get("actor_train/mfu", 0.0)), 5) for s in hist
        ],
        "new_dense_fallback_warnings": sorted(
            str(k) for k in transformer._warned_dense - warned_before
        ),
        **live,
    }
    assert all(np.isfinite(losses)), losses
    gc.collect()
    report["seconds"] = round(time.perf_counter() - t_phase, 2)
    report["hbm_peak_gb"] = hbm_peak_gb()
    if clock:
        report.update(clock.since(snap))
    return report


# ---------------------------------------------------------------------------
# --chips 4: the sharded phase and what it is compared with
# ---------------------------------------------------------------------------


def _shard_devices(tree):
    """{device id: bytes held} over every addressable shard of a tree."""
    import jax

    held = {}
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def inspect_placement(workers) -> dict:
    """From ``addressable_shards``: trainer parameters and optimizer state
    sit on the trainer's chips only, server weights and KV pool on the
    server's only, each split (no chip holds a whole tree), and nothing of
    either is whole on chip 0."""
    import jax

    from areal_tpu.system.generation_server import GenerationServerWorker
    from areal_tpu.system.model_worker import ModelWorker

    gen = next(w for w in workers if isinstance(w, GenerationServerWorker))
    mw = next(w for w in workers if isinstance(w, ModelWorker))
    trainer = _actor_engine(mw)
    eng = gen.engine
    ids = [d.id for d in jax.devices()]
    train_ids = sorted(d.id for d in trainer.mesh.devices.flat)
    gen_ids = sorted(d.id for d in eng.mesh.devices.flat)
    assert train_ids == ids[:2] and gen_ids == ids[2:4], (train_ids, gen_ids)

    def whole(tree):
        return sum(x.nbytes for x in jax.tree.leaves(tree))

    out = {"trainer_devices": train_ids, "server_devices": gen_ids}
    for name, tree, where in (
        ("trainer_params", trainer.params, train_ids),
        ("trainer_opt_state", trainer.opt_state, train_ids),
        ("server_params", eng.params, gen_ids),
        ("server_kv_pool", (eng.k_pool, eng.v_pool), gen_ids),
    ):
        held = _shard_devices(tree)
        total = whole(tree)
        assert sorted(held) == where, (name, held, where)
        # split, not replicated: every chip holds well under the whole
        for dev, nbytes in held.items():
            assert nbytes < 0.75 * total, (name, dev, nbytes, total)
        out[name] = {
            "total_gb": round(total / 1e9, 3),
            "per_device_gb": {
                str(d): round(b / 1e9, 3) for d, b in sorted(held.items())
            },
        }
    return out


def compare_tp_server(model_cfg: dict, n_layers: int, seed: int) -> dict:
    """Greedy tokens and log-probabilities of a few requests: a TP=2
    engine on chips 2-3 vs a one-chip engine on chip 0, same weights."""
    import jax
    import numpy as np

    from areal_tpu.api.config import ModelName
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.backend import cast_floating, make_model
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import paged

    cfg_dict = dict(model_cfg, n_layers=n_layers)
    model = make_model(_model_abs(cfg_dict, seed), ModelName("actor"), None)
    params = cast_floating(model.init_params, model.model_cfg.dtype)
    devs = jax.devices()
    rng = np.random.default_rng(seed + 1)
    prompts = [
        rng.integers(3, model_cfg["vocab_size"], (n,)).tolist()
        for n in (1300, 200, 40)
    ]
    gconfig = GenerationHyperparameters(max_new_tokens=64, greedy=True)

    def run(**place):
        eng = ContinuousBatchingEngine(
            model.model_cfg, params, max_batch=4, kv_cache_len=2048,
            chunk_size=16, sampling=SamplingParams(greedy=True),
            cache_mode="paged", page_size=1024, prefill_chunk_tokens=1024,
            **place,
        )
        for i, p in enumerate(prompts):
            eng.submit(
                APIGenerateInput(
                    qid=f"r{i}", prompt_ids=p, input_ids=p, gconfig=gconfig
                )
            )
        outs = {}
        while len(outs) < len(prompts):
            eng.step()
            outs.update(eng.drain_results())
        used_kernel = bool(eng._use_paged_kernel)
        eng.close()
        return [outs[f"r{i}"] for i in range(len(prompts))], used_kernel

    one, k1 = run(device=devs[0])
    tp, k2 = run(mesh=MeshSpec(model=2).make_mesh(devs[2:4]))
    rows = []
    for a, b in zip(one, tp):
        ta, tb = list(a.output_ids), list(b.output_ids)
        n = min(len(ta), len(tb))
        agree = next((i for i in range(n) if ta[i] != tb[i]), n)
        la = np.asarray(a.output_logprobs[:agree], np.float32)
        lb = np.asarray(b.output_logprobs[:agree], np.float32)
        rows.append(
            {
                "prompt_len": len(a.prompt_ids),
                "tokens": n,
                "agree_prefix": agree,
                "max_abs_logp_diff": round(
                    float(np.abs(la - lb).max()) if agree else 0.0, 5
                ),
            }
        )
    return {
        "requests": rows,
        "paged_kernel": [k1, k2],
        "kernel_interpret": bool(paged.kernel_interpret()),
        "tolerance": {"max_abs_logp": TP_LOGP_MAX_ABS, "first_token": "equal"},
    }


def compare_fsdp_loss(model_cfg: dict, n_layers: int, seed: int) -> dict:
    """First train step's loss on a 2-chip FSDP mesh vs a one-chip
    forward of the same seeded batch over the same initial weights.  (A
    7B-width trainer with Adam fits no single chip at any depth — its
    embedding and head alone are 1.09B parameters x 16 B — so the
    one-chip side is a forward-only engine.)"""
    import jax
    import numpy as np

    from areal_tpu.api.config import ModelName
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.backend import make_model
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.train_engine import TrainEngine
    from areal_tpu.interfaces.ppo_interface import model_logprobs_fwd
    from areal_tpu.interfaces.sft_interface import sft_loss_fn
    from areal_tpu.models import transformer

    cfg_dict = dict(model_cfg, n_layers=n_layers, remat=True)
    model = make_model(_model_abs(cfg_dict, seed), ModelName("actor"), None)
    devs = jax.devices()
    rng = np.random.default_rng(seed + 2)
    lens = [1100, 900, 1300, 700]
    total = sum(lens)
    sample = SequenceSample.from_default(
        seqlens=lens,
        ids=[f"s{i}" for i in range(len(lens))],
        data={
            "packed_input_ids": rng.integers(
                3, model_cfg["vocab_size"], (total,)
            ).astype(np.int64),
            "prompt_mask": np.zeros((total,), bool),
        },
    )
    mb = MicroBatchSpec(max_tokens_per_mb=4096)

    one = TrainEngine(
        model.model_cfg, MeshSpec().make_mesh(devs[2:3]), model.init_params,
        optimizer_cfg=None, name="one",
    )
    logp = one.forward_batch(
        sample, model_logprobs_fwd(1.0), mb, output_shift=1
    )
    loss_one = float(-np.mean(logp))
    del one
    transformer.set_ambient_mesh(None)
    gc.collect()

    fsdp = TrainEngine(
        model.model_cfg, MeshSpec(fsdp=2).make_mesh(devs[:2]),
        model.init_params, optimizer_cfg=OptimizerConfig(lr=1e-5),
        total_train_steps=10, name="fsdp",
    )
    stats = fsdp.train_batch(sample, sft_loss_fn, mb)
    loss_fsdp = float(stats["loss"])
    held = _shard_devices(fsdp.params)
    del fsdp
    transformer.set_ambient_mesh(None)
    gc.collect()
    return {
        "loss_one_chip_forward": round(loss_one, 5),
        "loss_fsdp2_first_step": round(loss_fsdp, 5),
        "rel_diff": round(abs(loss_one - loss_fsdp) / abs(loss_one), 6),
        "tolerance_rel": FSDP_LOSS_REL,
        "fsdp_param_bytes_per_device": {str(k): v for k, v in held.items()},
    }


def phase_sharded(
    model_cfg: dict, seed: int, workdir: str, *, n_layers: int,
    clock: CompileClock = None, **async_kw,
) -> dict:
    """Trainer on a 2-chip ``fsdp`` mesh, TP=2 generation server on the
    other two (``gen.d1m2+d1f2m1``), and the one-chip comparisons."""
    t0 = time.perf_counter()
    snap = clock.snapshot() if clock else None
    report = {"phase": "sharded", "layers": n_layers}
    report["async_ppo"] = phase_async_ppo(
        model_cfg, seed, workdir, n_layers=n_layers,
        allocation_mode="gen.d1m2+d1f2m1", trial="sharded",
        inspect=inspect_placement, **async_kw,
    )
    report["tp_server_vs_one_chip"] = compare_tp_server(
        model_cfg, n_layers, seed
    )
    report["fsdp_vs_one_chip"] = compare_fsdp_loss(model_cfg, n_layers, seed)
    report["seconds"] = round(time.perf_counter() - t0, 2)
    report["hbm_peak_gb"] = hbm_peak_gb()
    if clock:
        report.update(clock.since(snap))
    return report


# ---------------------------------------------------------------------------
# main: the chip-only assertions live here
# ---------------------------------------------------------------------------


def check_serve(r: dict):
    assert r["paged"], "cache_mode=auto did not take the paged pool"
    assert r["use_paged_kernel"] and not r["kernel_interpret"], (
        "the server did not run the COMPILED paged kernel: "
        f"use_paged_kernel={r['use_paged_kernel']} "
        f"interpret={r['kernel_interpret']}"
    )
    assert r["weight_dtype"] == "bfloat16", r["weight_dtype"]
    assert max(r["prompt_lens"]) > r["prefill_chunk_tokens"]
    ref = r["reference"]
    assert ref["max_abs_diff"] <= SERVE_LOGP_MAX_ABS, ref
    assert ref["mean_abs_diff"] <= SERVE_LOGP_MEAN_ABS, ref


def check_async(r: dict, train_steps: int):
    assert r["train_steps"] >= train_steps, r["train_steps"]
    assert r["areal_train_mfu"] > 0, (
        "areal_train_mfu is 0: the device's peak was not known"
    )
    assert r["server_version"] >= 2 and r["swaps_total"] >= 2, (
        "the server did not adopt 2 weight publishes", r
    )
    assert r["swap_recomputed_rows"] > 0, (
        "no weight update landed while rows were in flight", r
    )
    assert r["server_use_paged_kernel"], "server fell off the paged kernel"
    assert not r["new_dense_fallback_warnings"], (
        "the trainer fell back to dense attention", r
    )
    assert max(r["tokens_per_step"]) >= 2048, r["tokens_per_step"]


def check_sharded(r: dict, train_steps: int):
    check_async(r["async_ppo"], train_steps)
    assert r["async_ppo"]["trainer_mesh"] == {"fsdp": 2}, r["async_ppo"]
    tp = r["tp_server_vs_one_chip"]
    assert all(tp["paged_kernel"]) and not tp["kernel_interpret"], tp
    for row in tp["requests"]:
        assert row["agree_prefix"] >= 1, row  # first token equal
        assert row["max_abs_logp_diff"] <= TP_LOGP_MAX_ABS, row
    assert r["fsdp_vs_one_chip"]["rel_diff"] <= FSDP_LOSS_REL, r


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded phase and what it is compared with",
    )
    args = p.parse_args(argv)

    from areal_tpu.base import _native
    from areal_tpu.base.compile_cache import (
        cache_entry_count,
        setup_compile_cache,
    )

    cache_dir = setup_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke.py needs a TPU: jax.devices()[0] is "
            f"{dev.platform!r} ({dev.device_kind})",
            file=sys.stderr,
        )
        return 2
    n_dev = len(jax.devices())
    if n_dev != args.chips:
        print(
            f"chip_smoke.py --chips {args.chips} needs exactly that many "
            f"chips; jax sees {n_dev}",
            file=sys.stderr,
        )
        return 2
    import jaxlib

    hbm = dev.memory_stats()["bytes_limit"]
    entries_before = cache_entry_count(cache_dir)
    emit(
        {
            "phase": "start",
            "device_kind": dev.device_kind,
            "devices": n_dev,
            "hbm_bytes_limit": hbm,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries": entries_before,
            "datapack": _native.backend(),
            "seed": args.seed,
        }
    )
    clock = CompileClock()
    workdir = os.path.join(REPO, ".smoke_work")
    # 8 steps, not 3: compile-free steps take about a second and a swap
    # that recomputes in-flight rows several, so a short run can end with
    # every publish but the last superseded before the server took it
    train_steps = 8
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            r = emit(
                phase_serve(QWEN25_1P5B, args.seed, workdir, clock=clock)
            )
            check_serve(r)
            fit = fit_layers(QWEN25_1P5B, hbm)
            emit({"phase": "fit_layers", **fit})
            r = emit(
                phase_async_ppo(
                    QWEN25_1P5B, args.seed, workdir,
                    n_layers=fit["layers"], train_steps=train_steps,
                    clock=clock,
                )
            )
            check_async(r, train_steps)
        else:
            # 7B widths leave no slack: embedding + head alone are 1.09B
            # parameters, 8 B each per trainer chip.  One layer fits only
            # against 90% of HBM with a 2.5 GB reserve (one chip: 85%, 3 GB)
            fit = fit_layers(
                QWEN25_7B, hbm, trainer_chips=2, server_shares_chip=False,
                reserve=2.5e9, budget_frac=0.9,
            )
            emit({"phase": "fit_layers", **fit})
            r = emit(
                phase_sharded(
                    QWEN25_7B, args.seed, workdir, n_layers=fit["layers"],
                    train_steps=train_steps, clock=clock,
                )
            )
            check_sharded(r, train_steps)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit(
        {
            "phase": "done",
            "seconds": round(time.perf_counter() - t0, 2),
            **clock.since((0.0, 0, 0)),
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_before": entries_before,
            "compile_cache_entries_after": cache_entry_count(cache_dir),
        }
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": n_dev,
                },
            }
        ),
        flush=True,
    )
    return 0


def stop_children():
    """Stop every process this one started and that is still running (the
    math verifier's forked worker pool): the script leaves nothing behind."""
    import signal

    me = os.getpid()
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid == me:
                os.kill(int(entry), signal.SIGKILL)
        except (OSError, ValueError, IndexError):
            continue  # gone already, or not ours to read


if __name__ == "__main__":
    # worker threads (zmq pollers, orbax commit threads, rollout loops)
    # must not keep a finished OR FAILED run alive: a failure is printed
    # and the process leaves at once with a non-zero code — a phase's
    # failure is never caught and carried on from
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 - reported, then exit != 0
        import traceback

        traceback.print_exc()
        rc = 1
    stop_children()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
