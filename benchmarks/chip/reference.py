"""Plain float32 forward of a benchmark configuration: the yardstick that
decides ``correct``.

Written out in ``jax.numpy`` from the published description: the
embedding, then each layer's forward as the configuration's family gives
it (``families/<family>.py``, on the helpers here), then RMSNorm at the
configuration's epsilon and an untied head.  It imports nothing of the
program; its weights come from ``weights.py`` with the run's seed, one
layer at a time, so a run holds one layer of float32 weights beside the
hidden states of the sequences it checks.

``quant="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 e4m3 (weights per output channel, activations
per row), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import cells
from weights import LeafMaker, layer_groups

QUERY_BLOCK = 512
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fake_quant(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis keeps one scale per output)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, contract_w, quant):
    """``x @ w`` over x's last axis and w's axes ``contract_w``."""
    if quant:
        x = _fake_quant(x, -1)
        w = _fake_quant(w, contract_w)
    return jnp.tensordot(x, w, axes=((x.ndim - 1,), contract_w))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm, head, *, eps, quant):
    h = _rms(x, norm.astype(jnp.float32), eps)
    return _mm(h, head.astype(jnp.float32), (1,), quant)


def logits_at(spec: dict, seed: int, seqs: list, want: list, length: int,
              quant: str | None = None) -> list:
    """Float32 logits of each token list in ``seqs`` at the positions
    ``want[i]`` (the positions whose next token is checked).  Every
    sequence is padded to ``length`` tokens (a multiple of 512), so one
    compiled layer serves every run; causal attention keeps the padding
    out of the positions read."""
    if length % QUERY_BLOCK:
        raise ValueError(f"length {length} is not a multiple of "
                         f"{QUERY_BLOCK}")
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    fam = cells.family(spec["family"])
    eps = float(spec["rms_norm_eps"])
    fp8 = quant == "fp8"
    leaves = LeafMaker(spec, seed)
    with jax.default_matmul_precision("highest"):
        embed = leaves.top("embed")
        xs = []
        for toks in seqs:
            if not 0 < len(toks) <= length:
                raise ValueError(f"sequence of {len(toks)} tokens does not "
                                 f"fit {length}")
            ids = jnp.asarray(list(toks) + [0] * (length - len(toks)),
                              jnp.int32)
            xs.append(jnp.take(embed, ids, axis=0).astype(jnp.float32))
        del embed
        for kind, layers in layer_groups(spec):
            for i in layers:
                w = leaves.layer(kind, i)
                xs = [fam.layer(kind, w, x, spec, fp8) for x in xs]
                del w
        norm, head = leaves.top("final_norm"), leaves.top("lm_head")
        return [_head(x[jnp.asarray(pos, jnp.int32)], norm, head, eps=eps,
                      quant=fp8)
                for x, pos in zip(xs, want)]
