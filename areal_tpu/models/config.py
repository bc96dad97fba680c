"""Transformer architecture config.

TPU-native analogue of the reference's ``ReaLModelConfig``
(reference: realhf/api/core/model_api.py — model config consumed by
realhf/impl/model/nn/real_llm_api.py:100).  One config dataclass covers all
supported HF families (llama/qwen2/qwen3/mistral/gemma/gpt2/mixtral); family
specific conversion lives in ``areal_tpu/models/hf/``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple


#: the kinds a layer of a stack stated by kind can be (``layer_types``)
LAYER_KINDS = (
    "attention", "window", "mamba", "latent", "mamba1", "gmu", "cross",
    "parallel", "latent_window",
)


#: the kinds that are plain attention over per-head K and V, full or
#: windowed: what the trainer's flash kernels (and so its backward) take
PLAIN_ATTENTION_KINDS = ("attention", "window")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    hidden_dim: int
    n_q_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_dim: int
    vocab_size: int
    max_position_embeddings: int = 32768

    # architecture knobs
    activation: str = "silu"  # silu | gelu | relu
    norm_type: str = "rms"  # rms | layer
    norm_eps: float = 1e-6
    rotary_base: float = 10000.0
    use_attention_bias: bool = False  # qwen2-style qkv bias
    use_mlp_bias: bool = False
    gated_mlp: bool = True  # SwiGLU-style; False = plain fc->act->proj (gpt2)
    tied_embedding: bool = False
    use_qk_norm: bool = False  # qwen3-style per-head q/k RMSNorm
    embed_scale: Optional[float] = None  # gemma multiplies embeddings
    abs_position_embedding: bool = False  # gpt2
    sliding_window: Optional[int] = None  # mistral
    # a LOOPED dense stack (ouro ``total_ut_steps``): the ``n_layers``
    # layers are run ``loop_steps`` times with the SAME weights before a
    # token's logits exist, the final norm after every pass; the attention
    # of pass r, layer l reads what pass r, layer l wrote for the earlier
    # tokens, so the cache holds ``n_layers x loop_steps`` layers
    # (``n_attn_layers``; cache layer ``r * n_layers + l``)
    loop_steps: int = 1
    # sandwich norms (ouro): a norm on each branch's OUTPUT before the
    # residual add (``attn_post_norm`` / ``mlp_post_norm``), beside the
    # norm on its input
    sandwich_norm: bool = False
    # the looped stack's exit gate ([hidden -> 1] after every pass's
    # norm): a token leaves at the first pass whose cumulated exit
    # probability reaches ``loop_exit_threshold``.  At the published 1
    # that is the last pass for every token, and the gate's weights are
    # held and converted but never evaluated; any other value is refused
    loop_exit_gate: bool = False
    loop_exit_threshold: float = 1.0

    # MoE (mixtral / qwen3-moe); n_experts=0 disables
    n_experts: int = 0
    n_experts_per_tok: int = 2
    moe_intermediate_dim: Optional[int] = None
    moe_aux_loss_coef: float = 0.001
    moe_z_loss_coef: float = 0.0
    # renormalize the top-k routing probs to sum to 1 (mixtral: yes;
    # qwen3-moe: per-config ``norm_topk_prob``)
    moe_norm_topk_prob: bool = True

    # a shared expert beside the routed ones: every token, weight 1
    # (granitemoehybrid ``shared_intermediate_size``); 0 = none
    shared_expert_dim: int = 0
    # "softmax_topk": softmax over all experts, then the top k (mixtral,
    # qwen3-moe); "topk_softmax": the top k LOGITS, softmax over those k;
    # "sigmoid_group": sigmoid scores, the choice by score + a learned
    # bias among the best ``moe_topk_groups`` of ``moe_n_groups`` groups
    # of consecutive experts, weights the unbiased scores renormalised
    # times ``moe_routed_scale`` (deepseek_v3 ``noaux_tc``)
    moe_router: str = "softmax_topk"
    moe_n_groups: int = 1
    moe_topk_groups: int = 1
    moe_routed_scale: float = 1.0
    # the experts THIS program holds, [first, first + held) of n_experts
    # (one chip's share of a deployment that divides each layer's experts
    # over chips).  The router keeps n_experts outputs and its top k; a
    # pair routed to an absent expert adds nothing.  None = all of them.
    moe_first_expert: int = 0
    moe_held_experts: Optional[int] = None

    # a stack stated by kind (models/hybrid.py): one of LAYER_KINDS per
    # layer, in the published order; None = every layer is the attention
    # layer of models/transformer.py.
    # A "window" layer is an attention layer that attends the last
    # ``sliding_window`` positions (``i - j < sliding_window``) and whose
    # pages the engine releases once every holder's window has passed them.
    # A "mamba1" layer is a selective-scan mixer whose decay differs by
    # channel AND by state index (``mamba_d_state`` x ``mamba_d_inner``
    # with ``mamba_head_dim`` 1: a channel is a head of one).  A "cross"
    # layer is an attention layer with queries only: it reads the K and V
    # of ``kv_shared_layer``, the one "attention" layer before it, from
    # that layer's pages.  A "gmu" layer gates ``memory_layer``'s scan
    # output (the last "mamba1" layer's, before ITS gate) by a projection
    # of its own input, and caches nothing.  A "parallel" layer runs an
    # attention mixer AND a Mamba-2 mixer side by side on ONE normed input
    # and adds both to the residual stream (falcon_h1): it has a number
    # among the attention mixers and one among the Mamba mixers, pages in
    # the pool of whole-context pages and a state slot
    layer_types: Optional[Tuple[str, ...]] = None
    # differential heads: adjacent heads pair up, a pair's two softmax
    # maps are subtracted under a learned weight, and the pair's output
    # goes through an RMS norm of twice ``head_dim`` (phi4flash).  A
    # pair's ``[k1 | k2]`` and ``[v1 | v2]`` are ONE cached head
    diff_attention: bool = False
    # per layer of a stack stated by kind: whether its attention mixer
    # ropes q and k (smallthinker ``rope_layout``: the global layers have
    # no position term); None = every layer follows ``use_rope``
    rope_layers: Optional[Tuple[bool, ...]] = None
    # what an expert layer's ROUTER reads: "mlp" = the experts' own input
    # (the norm of the layer's second half); "attn" = the mixer's input
    # (the norm of its first half: smallthinker places the router before
    # attention), while the experts still read the second norm
    moe_router_input: str = "mlp"
    # the first ``n_dense_layers`` layers of a stack stated by kind have
    # a dense MLP of ``intermediate_dim`` where the others have experts
    # (deepseek_v3 ``first_k_dense_replace``)
    n_dense_layers: int = 0
    # latent attention (MLA): queries through a rank-``q_lora_rank``
    # bottleneck; keys and values expanded from ONE latent of
    # ``kv_lora_rank`` a token, beside ONE roped key part of
    # ``qk_rope_head_dim`` shared by all heads.  A query/key head is
    # ``head_dim`` = qk_nope_head_dim + qk_rope_head_dim wide, a value
    # head ``v_head_dim``.  The cache holds [latent | roped part] a token
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a "latent_window" layer is a latent layer under ``i - j <
    # sliding_window`` at widths OF ITS OWN (dots3_note's ``swa_`` keys:
    # heads, both ranks, head sizes and RoPE base differ from the full
    # layers'), with a parameter stack of its own and latent pages in the
    # window pool.  ``swa_head_dim`` = its nope + rope widths
    swa_n_q_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rotary_base: Optional[float] = None
    # a plain "window" layer at a query-head count OF ITS OWN
    # (``swa_n_q_heads`` > 0 beside kind "window": laguna's 64 against 48
    # on the same 8 KV heads of 128) has a parameter stack of its own
    # too, plain RoPE over its whole head at ``swa_rotary_base`` and the
    # gate ``swa_attention_gate`` says (``window_plain()``), while the
    # "attention" layers keep ``rotary_base``, YaRN where
    # ``rope_yarn_factor`` is set and ``rope_partial_dim``: the leading
    # columns of a head that are rotated (``partial_rotary_factor`` times
    # ``head_dim``; 0 = the whole head)
    rope_partial_dim: int = 0
    # the learned indexer of the "latent" layers (DeepSeek-V3.2-Exp's
    # lightning indexer): ``index_n_heads`` query heads of
    # ``index_head_dim`` from the query latent, ONE key of that width a
    # token (cached beside its latent entry), a weight a head from the
    # layer's input; a query attends the ``index_topk`` positions of its
    # context with the largest ``sum_j w_j relu(q_j . k)`` and nothing
    # else (every position while the context is shorter).  0 = no indexer
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # "headwise": one sigmoid gate a head from the layer's normed input,
    # on the head's output before ``W_o`` (``attention_gate_type``; the
    # window layers' own key is ``swa_attention_gate_type``)
    attention_gate: Optional[str] = None
    swa_attention_gate: Optional[str] = None
    # the normed query latent times ``sqrt(hidden / q_lora_rank)``, the
    # normed key-value latent times ``sqrt(hidden / kv_lora_rank)``
    # (``apply_mla_qkv_lora_rescale``, read as LongCat-Flash's rule)
    mla_lora_rescale: bool = False
    # multi-token-prediction modules the checkpoint carries after its
    # ``n_layers`` layers (deepseek_v3 ``num_nextn_predict_layers``): a
    # drafting head, not served; the adapter skips their weights by name
    n_mtp_modules: int = 0
    # YaRN (``rope_scaling`` of type "yarn"); factor None = plain RoPE
    rope_yarn_factor: Optional[float] = None
    rope_yarn_original_max: int = 4096
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # Mamba mixer sizes (d_inner = mamba_n_heads * mamba_head_dim).  A
    # "mamba1" layer's ``dt`` comes through a rank-``mamba_dt_rank``
    # bottleneck; its scan goes token by token (no ``mamba_chunk_size``)
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_dt_rank: int = 0
    # granite's multipliers: softmax scale of attention (None =
    # 1/sqrt(head_dim)), the factor on every residual branch, and the
    # divisor of the logits; ``embed_scale`` above is the fourth
    attention_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    logits_divisor: Optional[float] = None
    # False = no position term at all (NoPE)
    use_rope: bool = True
    # falcon_h1's muP multipliers, each a published number (None = 1; the
    # embedding's is ``embed_scale`` and the head's the reciprocal of
    # ``logits_divisor``).  A mixer reads ``in`` times the layer's normed
    # input and adds ``out`` times its output projection; keys are scaled
    # by ``key_scale`` before their rope; ``ssm_scales`` are the factors
    # of the Mamba-2 in-projection's five segments ``(z, x, B, C, dt)``;
    # ``mlp_scales`` scale the gate's pre-activation and the down
    # projection's output
    attn_in_scale: Optional[float] = None
    attn_out_scale: Optional[float] = None
    key_scale: Optional[float] = None
    ssm_in_scale: Optional[float] = None
    ssm_out_scale: Optional[float] = None
    ssm_scales: Optional[Tuple[float, ...]] = None
    mlp_scales: Optional[Tuple[float, float]] = None

    # head
    is_critic: bool = False  # value head (dim 1) instead of lm head

    # numerics
    dtype: str = "bfloat16"  # activation/param dtype on device
    logits_dtype: str = "float32"
    # rematerialize each layer in backward (jax.checkpoint over the layer
    # scan) — trades FLOPs for activation memory, standard for training.
    remat: bool = False
    # what the layer-checkpoint keeps — a graduated preset table
    # (areal_tpu/models/remat.py), smallest device footprint first:
    # "none" = full recompute; "offload_qkv" = save q/k/v + attn output to
    # HOST memory (qkv_attn's FLOP savings at none's HBM footprint);
    # "attn_out" = save the attention-block output only; "mlp" = save both
    # block boundaries (attn_out + mlp_out); "qkv_attn" = save q/k/v
    # projections + attention output (v5p-class memory); "dots" = save
    # every matmul output (cheapest backward, most memory).
    remat_policy: str = "none"
    # context-parallel attention over a sharded `seq` mesh axis:
    # "ring" rotates KV blocks with n ppermutes (scales to any length);
    # "ulysses" pays two all-to-alls and runs full attention on a head
    # subset (fewer collectives; needs per-device q heads % cp degree == 0)
    cp_impl: str = "ring"
    # pipeline micro-batches per forward when the mesh has a ``pipe`` axis
    # (row groups rotated stage-to-stage; areal_tpu/parallel/pipeline.py).
    # 0 = auto (2 x pipe stages, capped by the row count).
    pipe_microbatches: int = 0
    # pipeline schedule: "gpipe" (differentiate through the forward scan;
    # saves ~m micro-batch boundary activations) or "1f1b" (custom-VJP
    # interleaved backward; live activations bound by ~2p micro-batches at
    # the cost of one extra forward sweep — the memory-bounded schedule
    # for large micro-batch counts).  MoE models require "gpipe" (router
    # aux losses are not differentiated under 1f1b).
    pipe_schedule: str = "gpipe"

    def __post_init__(self):
        if self.layer_types is not None:
            # a JSON list hashes as a tuple (the config is a static jit arg)
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            assert len(self.layer_types) == self.n_layers, (
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}"
            )
            kinds = set(self.layer_types)
            assert kinds <= set(LAYER_KINDS), (
                f"layer_types {sorted(kinds - set(LAYER_KINDS))}: a layer "
                f"is one of {LAYER_KINDS}"
            )
            # one page format a POOL: the pool of whole-context pages
            # holds per-head K and V or latent entries, and so does the
            # window pool ("parallel" is refused beside "latent" below)
            assert not (
                "latent" in kinds and kinds & {"attention", "cross"}
            ), self.layer_types
            assert not {"window", "latent_window"} <= kinds, self.layer_types
            # the two programs' chunk buffers are written for per-head
            # pages in both pools or latent entries in both
            assert not ("latent" in kinds and "window" in kinds) and not (
                "latent_window" in kinds and "latent" not in kinds
            ), self.layer_types
            # one state format the slots: Mamba-2's or Mamba-1's
            assert not {"mamba", "mamba1"} <= kinds, self.layer_types
            # a parallel layer's halves are per-head pages and Mamba-2
            assert not (
                "parallel" in kinds and kinds & {"latent", "mamba1"}
            ), self.layer_types
            if "latent" in kinds:
                assert self.kv_lora_rank > 0 and self.qk_rope_head_dim > 0
            if kinds & {"window", "latent_window"}:
                assert self.sliding_window and self.sliding_window > 1
            if self.window_has_own_widths:
                assert self.swa_n_q_heads % self.n_kv_heads == 0, (
                    self.swa_n_q_heads, self.n_kv_heads,
                )
            assert 0 <= self.rope_partial_dim <= self.head_dim and (
                self.rope_partial_dim % 2 == 0
            ), self.rope_partial_dim
            if "latent_window" in kinds:
                assert (
                    self.swa_n_q_heads > 0 and self.swa_kv_lora_rank > 0
                    and 0 < self.swa_qk_rope_head_dim < self.swa_head_dim
                    and self.swa_v_head_dim > 0
                ), "latent_window layers: the swa_ widths"
            if self.index_topk:
                assert "latent" in kinds and self.q_lora_rank > 0, (
                    "the indexer reads a latent layer's query latent"
                )
                assert self.index_n_heads > 0 and (
                    self.index_head_dim >= self.qk_rope_head_dim
                )
            if "mamba1" in kinds:
                assert self.mamba_head_dim == 1 and self.mamba_dt_rank > 0
            if "cross" in kinds:
                first = self.layer_types.index("cross")
                assert self.layer_types[:first].count("attention") == 1 and (
                    "attention" not in self.layer_types[first:]
                ), "cross layers read the ONE attention layer before them"
            if "gmu" in kinds:
                assert "mamba1" in self.layer_types[
                    : self.layer_types.index("gmu")
                ], "a gmu layer gates the scan output of a mamba1 layer"
        assert self.loop_steps >= 1, self.loop_steps
        if self.loop_steps > 1:
            assert self.layer_types is None and not self.is_moe, (
                "loop_steps > 1: a dense stack of models/transformer.py "
                "(no stack stated by kind and no expert layer loops)"
            )
        if self.loop_exit_threshold != 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.loop_exit_threshold}: only the "
                "published 1 is served (every token leaves at the last "
                "pass); below it rows leave the loop at different passes, "
                "and a step no longer costs every row the same"
            )
        if self.n_mamba_layers and not self.is_mamba1:
            # a B/C group serves a whole number of heads
            assert self.mamba_n_heads % self.mamba_n_groups == 0, (
                self.mamba_n_heads, self.mamba_n_groups,
            )
        for name, n in (("ssm_scales", 5), ("mlp_scales", 2)):
            if getattr(self, name) is not None:
                object.__setattr__(
                    self, name, tuple(float(m) for m in getattr(self, name))
                )
                assert len(getattr(self, name)) == n, (name, getattr(self, name))
        # the multipliers are read by the Mamba-2 mixer and the dense MLP
        assert not (
            self.is_mamba1
            and (self.ssm_in_scale or self.ssm_out_scale or self.ssm_scales)
        ), "ssm multipliers: the Mamba-2 mixer's"
        assert self.mlp_scales is None or not self.n_experts, (
            "mlp_scales: the dense MLP's"
        )
        if self.diff_attention:
            assert self.n_kv_heads % 2 == 0 and not self.use_qk_norm
        if self.rope_layers is not None:
            assert self.layer_types is not None, "rope_layers: a stack by kind"
            object.__setattr__(
                self, "rope_layers", tuple(bool(r) for r in self.rope_layers)
            )
            assert len(self.rope_layers) == self.n_layers, self.rope_layers
        assert self.moe_router_input in ("mlp", "attn")
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.moe_router in (
            "softmax_topk", "topk_softmax", "sigmoid_group"
        )
        if self.moe_router == "sigmoid_group":
            assert self.n_experts % self.moe_n_groups == 0
            assert self.moe_topk_groups <= self.moe_n_groups
        if self.moe_held_experts is not None:
            assert (
                0 <= self.moe_first_expert
                and self.moe_first_expert + self.moe_held_experts
                <= self.n_experts
            ), (self.moe_first_expert, self.moe_held_experts, self.n_experts)
        assert self.n_q_heads % self.n_kv_heads == 0
        assert self.attention_gate in (None, "headwise")
        assert self.swa_attention_gate in (None, "headwise")
        assert self.activation in ("silu", "gelu", "relu")
        assert self.norm_type in ("rms", "layer")
        assert self.pipe_schedule in ("gpipe", "1f1b"), (
            f"unknown pipe_schedule {self.pipe_schedule!r}"
        )
        from areal_tpu.models.remat import POLICY_NAMES

        assert self.remat_policy in POLICY_NAMES, (
            f"unknown remat_policy {self.remat_policy!r} "
            f"(valid: {POLICY_NAMES})"
        )
        assert self.cp_impl in ("ring", "ulysses"), (
            f"unknown cp_impl {self.cp_impl!r}"
        )

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_hybrid(self) -> bool:
        """Layers of more than one kind: models/hybrid.py runs the stack."""
        return self.layer_types is not None

    @property
    def n_attn_layers(self) -> int:
        """CACHE layers: layers that WRITE per-token KV (every layer of a
        dense stack, once for every pass of a looped one: ``n_layers x
        loop_steps``; "attention", "window", "latent", "latent_window" and
        "parallel" layers of a stack by kind: a "cross" layer reads
        another's, a "gmu" layer has none).  What every pool shape, page
        byte count and handoff reads; the WEIGHT layers are ``n_layers``."""
        if self.layer_types is None:
            return self.n_layers * self.loop_steps
        return sum(
            t in ("attention", "window", "latent", "latent_window", "parallel")
            for t in self.layer_types
        )

    @property
    def n_window_layers(self) -> int:
        """Layers of a stack stated by kind whose pages follow the
        window's page rule (a pool and a table of their own)."""
        if self.layer_types is None:
            return 0
        return sum(t in ("window", "latent_window") for t in self.layer_types)

    @property
    def window_has_own_widths(self) -> bool:
        """The plain "window" layers have a query-head count, a rope rule
        and a parameter stack of their own (``window_plain()``)."""
        return (
            self.layer_types is not None
            and "window" in self.layer_types
            and self.swa_n_q_heads > 0
        )

    def window_plain(self) -> "TransformerConfig":
        """This config as a plain "window" layer's mixer reads it: itself,
        or with the window's own head count, RoPE base (plain, over the
        whole head) and gate in the places of the full layers'."""
        return _window_plain(self) if self.window_has_own_widths else self

    @property
    def n_mamba_layers(self) -> int:
        """Layers that keep a recurrent state per sequence (a "parallel"
        layer counts here AND in ``n_attn_layers``)."""
        if self.layer_types is None:
            return 0
        return sum(
            t in ("mamba", "mamba1", "parallel") for t in self.layer_types
        )

    @property
    def n_parallel_layers(self) -> int:
        if self.layer_types is None:
            return 0
        return sum(t == "parallel" for t in self.layer_types)

    @property
    def n_cross_layers(self) -> int:
        if self.layer_types is None:
            return 0
        return sum(t == "cross" for t in self.layer_types)

    @property
    def n_gmu_layers(self) -> int:
        if self.layer_types is None:
            return 0
        return sum(t == "gmu" for t in self.layer_types)

    @property
    def is_mamba1(self) -> bool:
        return self.layer_types is not None and "mamba1" in self.layer_types

    @property
    def kv_shared_layer(self) -> Optional[int]:
        """The layer whose K and V the "cross" layers read."""
        if not self.n_cross_layers:
            return None
        return self.layer_types.index("attention")

    @property
    def memory_layer(self) -> Optional[int]:
        """The layer whose scan output the "gmu" layers gate: the last
        "mamba1" layer before the first of them."""
        if not self.n_gmu_layers:
            return None
        first = self.layer_types.index("gmu")
        return max(
            l for l in range(first) if self.layer_types[l] == "mamba1"
        )

    @property
    def n_global_readers(self) -> int:
        """Layers that read the pool of whole-context pages each decode
        step (those that write it and the "cross" layers)."""
        return self.n_attn_layers - self.n_window_layers + self.n_cross_layers

    @property
    def pool_kv_heads(self) -> int:
        """Heads of a per-head page (a differential pair is one)."""
        return self.n_kv_heads // 2 if self.diff_attention else self.n_kv_heads

    @property
    def pool_head_dim(self) -> int:
        return 2 * self.head_dim if self.diff_attention else self.head_dim

    def layer_ropes(self, layer: int) -> bool:
        """Whether layer ``layer``'s attention mixer ropes q and k."""
        if self.rope_layers is None:
            return self.use_rope
        return self.rope_layers[layer]

    @property
    def is_latent(self) -> bool:
        """The paged layers cache ONE latent entry a token
        (``kv_latent_dim`` wide) where the others keep per-head K and V."""
        return self.layer_types is not None and "latent" in self.layer_types

    @property
    def kv_latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def is_latent_window(self) -> bool:
        """The window pool's page is a latent entry of the ``swa_`` widths."""
        return (
            self.layer_types is not None and "latent_window" in self.layer_types
        )

    @property
    def n_latent_window_layers(self) -> int:
        if self.layer_types is None:
            return 0
        return sum(t == "latent_window" for t in self.layer_types)

    @property
    def is_indexed(self) -> bool:
        """The latent layers attend an indexer's choice of positions and
        cache an index key a token beside the latent entry."""
        return self.index_topk > 0

    def window_latent(self) -> "TransformerConfig":
        """This config as a "latent_window" layer's mixer reads it: the
        ``swa_`` widths in the places of the full layers' (heads, ranks,
        head sizes, RoPE base, gate), no indexer."""
        return _window_latent(self)

    @property
    def qk_nope_head_dim(self) -> int:
        return self.head_dim - self.qk_rope_head_dim

    @property
    def n_expert_layers(self) -> int:
        """Layers with an expert block (all but the leading dense ones)."""
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    @property
    def n_held_experts(self) -> int:
        if self.moe_held_experts is None:
            return self.n_experts
        return self.moe_held_experts

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the causal conv: ``[x | B | C]`` (Mamba-2), ``x``
        alone (Mamba-1 makes B and C of the conv's output)."""
        if self.is_mamba1:
            return self.mamba_d_inner
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


@functools.lru_cache(maxsize=None)
def _window_latent(cfg: TransformerConfig) -> TransformerConfig:
    return dataclasses.replace(
        cfg,
        n_q_heads=cfg.swa_n_q_heads, n_kv_heads=cfg.swa_n_q_heads,
        q_lora_rank=cfg.swa_q_lora_rank, kv_lora_rank=cfg.swa_kv_lora_rank,
        head_dim=cfg.swa_head_dim, qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        v_head_dim=cfg.swa_v_head_dim,
        rotary_base=cfg.swa_rotary_base or cfg.rotary_base,
        attention_gate=cfg.swa_attention_gate,
        index_n_heads=0, index_head_dim=0, index_topk=0,
    )


@functools.lru_cache(maxsize=None)
def _window_plain(cfg: TransformerConfig) -> TransformerConfig:
    return dataclasses.replace(
        cfg,
        n_q_heads=cfg.swa_n_q_heads,
        rotary_base=cfg.swa_rotary_base or cfg.rotary_base,
        rope_yarn_factor=None, rope_partial_dim=0,
        attention_gate=cfg.swa_attention_gate,
    )


def tiny_config(
    vocab_size: int = 256, is_critic: bool = False, **kwargs
) -> TransformerConfig:
    """Small config for tests."""
    defaults = dict(
        n_layers=2,
        hidden_dim=32,
        n_q_heads=4,
        n_kv_heads=2,
        head_dim=8,
        intermediate_dim=64,
        vocab_size=vocab_size,
        max_position_embeddings=128,
        dtype="float32",
        is_critic=is_critic,
    )
    defaults.update(kwargs)
    return TransformerConfig(**defaults)
