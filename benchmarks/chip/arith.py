"""Operations and bytes the served work needs, from the configuration's
published sizes, and the chip peaks they are held against.

The counts depend on the blocks: each function hands over to the
configuration's family (``families/<family>.py``), so that the metrics
read every model through the same calls.
"""
from __future__ import annotations

import json
from pathlib import Path

import cells

HERE = Path(__file__).resolve().parent
BF16 = 2


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table['chips'])}")
    return table["chips"][device_kind]


def _family(spec: dict):
    return cells.family(spec["family"])


def layer_matmul_params(spec: dict) -> int:
    """Parameters one layer's tokens multiply."""
    return _family(spec).layer_matmul_params(spec)


def matmul_params(spec: dict) -> int:
    """Parameters every token multiplies: the layers and the head."""
    return _family(spec).matmul_params(spec)


def weight_bytes(spec: dict, rows_looked_up: int = 0) -> int:
    """Bytes of bfloat16 weights a step reads once: every layer (norms
    too), the final norm, the head, and ``rows_looked_up`` embedding
    rows."""
    return _family(spec).weight_bytes(spec, rows_looked_up)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of cache one token leaves in every layer together."""
    return _family(spec).kv_bytes_per_token(spec)


def attn_context(spec: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to (the window, if any)."""
    return _family(spec).attn_context(spec, pos)


def token_flops(spec: dict, pos: int) -> float:
    """Model operations of one token at position ``pos``."""
    return _family(spec).token_flops(spec, pos)


def prefill_flops(spec: dict, cached: int, prompt: int) -> float:
    """Operations of prefilling positions ``cached .. prompt-1``."""
    return _family(spec).prefill_flops(spec, cached, prompt)


def decode_least_bytes(spec: dict, contexts: list) -> int:
    """Least bytes one decode step over rows with these context lengths
    must move: every weight once, the embedding rows of the batch, and
    the cache of every live token of the active rows."""
    return _family(spec).decode_least_bytes(spec, contexts)


def decode_flops(spec: dict, contexts: list) -> float:
    """Operations of one decode step: a token at position ``c - 1`` per
    active row of context ``c``."""
    return _family(spec).decode_flops(spec, contexts)
