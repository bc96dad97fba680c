"""Bytes and operations of a decoder-hybrid-decoder stack (``phi4flash``:
Mamba-1 mixers, window attention, ONE full-attention layer whose K and V
the cross-attention layers read, gated memory units, a dense MLP a layer),
from its published sizes and the ``assumed_sizes`` beside them: what one
decode step must read of the weights, what one cached position costs the
paged kernel to read and to multiply, what a Mamba layer's recurrent state
costs a live row a step, BY LAYER KIND, and the least time these leave.
Keyed on the HuggingFace ``config.json`` names the configuration file
holds, like ``flops.py``, so the program can change and the yardstick
cannot.

One cached position of one layer is K and V of ``num_key_value_heads x
head_dim`` values each (20 x 64 x 2 x 2 B = 5,120 B in bf16; the program
may lay a differential pair out as one head of 128: the bytes are the
same).  The pool of whole-context pages has ONE layer, which every
``global reader`` (the full-attention layer and each cross layer, 8 of the
32) reads in every decode step; a window layer reads at most the
``sliding_window - 1`` positions before the query.  Every query head
multiplies its query with the key and its probability with BOTH halves of
its pair's value: ``40 x 64 x 2 + 40 x 128 x 2`` = 15,360 FLOP a position
as the equations state it (20,480 in the program's zero-padded form), 3-4
FLOP/B against a ridge of 240 on a v5e: the bytes decide.
"""

from __future__ import annotations

from benchmark.lib.reference_phi4flash import layer_kinds  # noqa: F401 - the stack by kind

KINDS = ("mamba1", "window", "attention", "gmu", "cross")


def as_run(config: dict) -> dict:
    """The published ``config.json`` keys of a configuration file (nothing
    is cut) with its ``assumed_sizes`` beside them: what the cell runs."""
    return dict(config["hf_config"], assumed_sizes=config["assumed_sizes"])


def counts(hf: dict) -> dict:
    kinds = layer_kinds(hf)
    return {k: kinds.count(k) for k in KINDS}


def global_readers(hf: dict) -> int:
    """Layers that read the pool of whole-context pages each decode step."""
    c = counts(hf)
    return c["attention"] + c["cross"]


def head_dim(hf: dict) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


def d_inner(hf: dict) -> int:
    return hf["assumed_sizes"]["expand"] * hf["hidden_size"]


def kv_bytes_per_token(hf: dict, bytes_per_el: int = 2) -> int:
    """K and V of one cached position of ONE layer."""
    return 2 * hf["num_key_value_heads"] * head_dim(hf) * bytes_per_el


def attn_flops_per_token(hf: dict) -> int:
    """What the equations multiply for ONE query row of every head against
    one cached position: a score of ``head_dim`` and a pair's value of
    twice that."""
    return hf["num_attention_heads"] * head_dim(hf) * (2 + 4)


def window_reads(hf: dict, context: int) -> int:
    """Cached positions a window layer's decode query at cached length
    ``context`` reads."""
    return min(context, hf["sliding_window"] - 1)


def mixer_params(hf: dict, kind: str) -> int:
    """Weight-matrix parameters of one layer's mixer (norms, biases, the
    lambdas and the skip left out: under 0.1%)."""
    d, di = hf["hidden_size"], d_inner(hf)
    sizes = hf["assumed_sizes"]
    q = d * hf["num_attention_heads"] * head_dim(hf)
    kv = 2 * d * hf["num_key_value_heads"] * head_dim(hf)
    if kind == "mamba1":
        n, r = sizes["d_state"], sizes["dt_rank"]
        return (
            d * 2 * di + di * (r + 2 * n) + r * di + di * d
            + n * di + sizes["d_conv"] * di
        )
    if kind == "gmu":
        return 2 * d * di
    if kind == "cross":
        return 2 * q  # queries and the output projection
    return 2 * q + kv


def param_count(hf: dict) -> int:
    """Matrix parameters of the model: its layers and the embedding (tied:
    the head is the same table)."""
    d = hf["hidden_size"]
    layers = sum(
        mixer_params(hf, kind) + 3 * d * hf["intermediate_size"]
        for kind in layer_kinds(hf)
    )
    return layers + hf["vocab_size"] * d


def weight_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode step has to read of the weights: every layer's
    matrices and the tied head read whole."""
    return param_count(hf) * bytes_per_param


def ssm_state_bytes(hf: dict) -> int:
    """One sequence's state in one Mamba layer, float32."""
    return 4 * hf["assumed_sizes"]["d_state"] * d_inner(hf)


def ssm_update_min_bytes(hf: dict, live_rows: float) -> float:
    """Least bytes of ONE execution of the decode step's state update (one
    layer, one step): each live row's state read and written.  ``A`` (one
    state's size a layer), dt, the input, B, C and the output are not
    counted."""
    return 2.0 * ssm_state_bytes(hf) * live_rows


def state_bytes_per_row_step(hf: dict) -> int:
    """What the recurrent state costs one live row one decode step over
    all Mamba layers: state and conv tail (bf16) read and written."""
    tail = 2 * d_inner(hf) * (hf["assumed_sizes"]["d_conv"] - 1)
    return counts(hf)["mamba1"] * 2 * (ssm_state_bytes(hf) + tail)


def decode_step_bytes(hf: dict, rows: float, context: float) -> dict:
    """Least bytes of ONE decode step of ``rows`` live rows at a mean
    cached length ``context``, by what is read."""
    c = counts(hf)
    kv = kv_bytes_per_token(hf)
    return {
        "weights": weight_bytes(hf),
        "shared_kv": global_readers(hf) * rows * context * kv,
        "window_kv": c["window"] * rows * window_reads(hf, int(context)) * kv,
        "state": rows * state_bytes_per_row_step(hf),
    }


def decode_min_seconds(
    hf: dict, decode_steps: float, row_steps: float,
    context_token_reads: float, window_token_reads: float,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps in
    which ``row_steps`` (row, step) pairs were live and whose queries
    together had ``context_token_reads`` cached positions before them, of
    which a window layer reads ``window_token_reads`` (:func:`window_reads`
    summed likewise): the weights once a step, the shared pages once a
    GLOBAL READER, the window pages once a window layer, the state twice a
    Mamba layer."""
    kv = kv_bytes_per_token(hf)
    total = (
        decode_steps * weight_bytes(hf)
        + row_steps * state_bytes_per_row_step(hf)
        + kv * global_readers(hf) * context_token_reads
        + kv * counts(hf)["window"] * window_token_reads
    )
    return total / hbm_bytes_per_s


def shared_kernel_min_seconds(hf: dict, ctx_tokens: float, peaks: dict) -> float:
    """Least time of ONE execution of the decode kernel over the pool of
    whole-context pages (one reading layer of one decode step) whose rows
    read ``ctx_tokens`` cached positions in all: the larger of its bytes
    over the bandwidth and its operations over the peak."""
    return max(
        ctx_tokens * kv_bytes_per_token(hf) / peaks["hbm_bytes_per_s"],
        ctx_tokens * attn_flops_per_token(hf) / peaks["bf16_flops"],
    )
