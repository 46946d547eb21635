"""Operations and bytes the served work requires, counted from the
configuration's sizes alone, whatever code implements the step.  These are
the numerators of ``serve_mfu`` and ``decode_hbm_share``; each counts the
least work a step must do, so neither share can pass 100%.

``sizes`` is a configuration file's ``model`` object (``d_model``,
``num_layers``, ``num_heads``, ``num_kv_heads``, ``head_dim``, ``d_ff``,
``vocab_size``, ``act``, ``dtype``).
"""
from __future__ import annotations

from typing import Dict, Sequence

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def padded_vocab(sizes: Dict) -> int:
    """The embedding tables' rows: the vocabulary padded to 128."""
    v = int(sizes["vocab_size"])
    return -(-v // 128) * 128


def dtype_bytes(sizes: Dict) -> int:
    return _BYTES[sizes.get("dtype", "bfloat16")]


def layer_linear_params(sizes: Dict) -> int:
    """Weights of one layer's projections: q, k, v, o and the MLP."""
    d, f = sizes["d_model"], sizes["d_ff"]
    hq, hkv, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    mlp = (3 if sizes.get("act", "gelu") == "silu" else 2) * d * f
    return attn + mlp


def linear_params(sizes: Dict) -> int:
    return sizes["num_layers"] * layer_linear_params(sizes)


def kv_bytes_per_position(sizes: Dict) -> int:
    """K and V of one position of one sequence, over all layers."""
    return (2 * sizes["num_layers"] * sizes["num_kv_heads"]
            * sizes["head_dim"] * dtype_bytes(sizes))


def attention_flops(sizes: Dict, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` (query, key) pairs in
    every layer: 2 x head_dim multiply-adds each, for QK and for PV."""
    return (4 * sizes["num_layers"] * sizes["num_heads"] * sizes["head_dim"]
            * pairs)


def unembed_flops(sizes: Dict, rows: int) -> int:
    return 2 * rows * sizes["d_model"] * sizes["vocab_size"]


def prefill_flops(sizes: Dict, prompt_len: int) -> int:
    """Model FLOPs of one prompt: every projection over every position,
    causal attention, and the unembed of the last position only."""
    s = int(prompt_len)
    return (2 * linear_params(sizes) * s
            + attention_flops(sizes, s * (s + 1) // 2)
            + unembed_flops(sizes, 1))


def decode_flops(sizes: Dict, kv_len: int) -> int:
    """Model FLOPs of one decoded token attending ``kv_len`` positions
    (its own included)."""
    return (2 * linear_params(sizes) + attention_flops(sizes, int(kv_len))
            + unembed_flops(sizes, 1))


def request_flops(sizes: Dict, prompt_len: int, tokens: int) -> int:
    """A served request: its prefill (which emits token 1), then
    ``tokens - 1`` decodes, token ``j`` attending ``prompt_len + j``."""
    total = prefill_flops(sizes, prompt_len)
    for j in range(1, int(tokens)):
        total += decode_flops(sizes, prompt_len + j)
    return total


def weight_bytes_per_step(sizes: Dict) -> int:
    """Weights a decode step must read once: the projections, the norms'
    gains and the unembed; the embedding table is read by row."""
    d, L = sizes["d_model"], sizes["num_layers"]
    params = (linear_params(sizes) + 2 * L * d + d
              + d * padded_vocab(sizes))
    return params * dtype_bytes(sizes)


def decode_step_bytes(sizes: Dict, kv_lens: Sequence[int]) -> int:
    """HBM bytes one decode step over slots attending ``kv_lens`` must
    move: every weight once, one embedding row per slot, each slot's valid
    K/V before this token, and the new K/V written."""
    n = len(kv_lens)
    per_pos = kv_bytes_per_position(sizes)
    return (weight_bytes_per_step(sizes)
            + n * sizes["d_model"] * dtype_bytes(sizes)
            + sum(int(kv) - 1 for kv in kv_lens) * per_pos
            + n * per_pos)
