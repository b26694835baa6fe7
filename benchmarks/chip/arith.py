"""Operations and bytes the served work needs, from the configuration's
published sizes, and the chip peaks they are held against.

The parameter arithmetic follows ``ArchConfig.param_counts`` of the
program (attention and a gated MLP per layer, an untied head), copied
here so that the yardstick stays fixed.  Attention is counted at the
published head count, whatever padding the program adds.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
BF16 = 2


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['chips'])}")
    return table["chips"][device_kind]


def layer_matmul_params(spec: dict) -> int:
    d, ff = spec["hidden_size"], spec["intermediate_size"]
    nq, nkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["head_dim"]
    return d * nq * dh + 2 * d * nkv * dh + nq * dh * d + 3 * d * ff


def matmul_params(spec: dict) -> int:
    """Parameters every token multiplies: the layers and the head."""
    return (spec["num_hidden_layers"] * layer_matmul_params(spec)
            + spec["vocab_size"] * spec["hidden_size"])


def weight_bytes(spec: dict, rows_looked_up: int = 0) -> int:
    """Bytes of bfloat16 weights a step reads once: every layer (norms
    too), the final norm, the head, and ``rows_looked_up`` embedding
    rows."""
    d = spec["hidden_size"]
    L = spec["num_hidden_layers"]
    per_layer = layer_matmul_params(spec) + 2 * d
    return BF16 * (L * per_layer + d + spec["vocab_size"] * d
                   + rows_looked_up * d)


def kv_bytes_per_token(spec: dict) -> int:
    return (BF16 * 2 * spec["num_hidden_layers"]
            * spec["num_key_value_heads"] * spec["head_dim"])


def attn_context(spec: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to (the window, if any)."""
    w = spec.get("sliding_window")
    return min(pos + 1, w) if w else pos + 1


def token_flops(spec: dict, pos: int) -> float:
    """Model operations of one token at position ``pos``: two per matmul
    parameter, and QK plus PV over its context at the published heads."""
    attn = (4 * spec["num_hidden_layers"] * spec["num_attention_heads"]
            * spec["head_dim"] * attn_context(spec, pos))
    return 2.0 * matmul_params(spec) + attn


def prefill_flops(spec: dict, cached: int, prompt: int) -> float:
    """Operations of prefilling positions ``cached .. prompt-1``."""
    n = prompt - cached
    if n <= 0:
        return 0.0
    w = spec.get("sliding_window")
    if w:
        return sum(token_flops(spec, p) for p in range(cached, prompt))
    # sum of (p + 1) over p in [cached, prompt)
    ctx = (prompt * (prompt + 1) - cached * (cached + 1)) // 2
    return (2.0 * matmul_params(spec) * n
            + 4.0 * spec["num_hidden_layers"]
            * spec["num_attention_heads"] * spec["head_dim"] * ctx)


def decode_least_bytes(spec: dict, contexts: list) -> int:
    """Least bytes one decode step over rows with these context lengths
    must move: every weight once, the embedding rows of the batch, and
    the K/V of every live token of the active rows."""
    return (weight_bytes(spec, rows_looked_up=len(contexts))
            + kv_bytes_per_token(spec) * sum(contexts))


def decode_flops(spec: dict, contexts: list) -> float:
    """Operations of one decode step: a token at position ``c - 1`` per
    active row of context ``c``."""
    return sum(token_flops(spec, c - 1) for c in contexts)
