"""Plain float32 forward of a benchmark configuration: the yardstick that
decides ``correct``.

A Llama/Mistral decoder written out in ``jax.numpy`` from the published
description: RMSNorm (the configuration's epsilon), rotary positions
(rotate-half, the configuration's theta), grouped-query causal attention
with the optional sliding window, a SwiGLU MLP, an untied head.  It
imports nothing of the program; its weights come from ``weights.py`` with
the run's seed, one layer at a time, so a run holds one layer of float32
weights beside the hidden states of the sequences it checks.

``quant="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 e4m3 (weights per output channel, activations
per row), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from weights import LeafMaker, dims

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


def _rope(x, pos, theta):
    """x: (S, H, dh); rotate-half convention."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (S, dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window):
    """q: (S, nq, dh); k, v: (S, nkv, dh); causal, query blocks of 512."""
    S, nq, dh = q.shape
    nkv = k.shape[1]
    g = nq // nkv
    qb = q.reshape(S // QUERY_BLOCK, QUERY_BLOCK, nkv, g, dh)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(dh)
        ok = kpos[None] <= qpos[:, None]
        if window is not None:
            ok &= qpos[:, None] - kpos[None] < window
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(block, (jnp.arange(S // QUERY_BLOCK), qb))
    return o.reshape(S, nq, dh)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "window",
                                              "quant"))
def _layer(w, x, *, eps, theta, window, quant):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    pos = jnp.arange(x.shape[0])
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], (0,), quant), pos, theta)
    k = _rope(_mm(h, w["wk"], (0,), quant), pos, theta)
    v = _mm(h, w["wv"], (0,), quant)
    o = _attention(q, k, v, window)
    x = x + _mm(o.reshape(o.shape[0], -1),
                w["wo"].reshape(-1, w["wo"].shape[-1]), (0,), quant)
    h = _rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], (0,), quant))
    a = a * _mm(h, w["w_up"], (0,), quant)
    return x + _mm(a, w["w_down"], (0,), quant)


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
    m = dims(spec)
    eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
    window = spec.get("sliding_window")
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
        for i in range(m["L"]):
            w = leaves.layer(i)
            xs = [_layer(w, x, eps=eps, theta=theta, window=window,
                         quant=fp8) for x in xs]
            del w
        norm, head = leaves.top("final_norm"), leaves.top("lm_head")
        return [_head(x[jnp.asarray(pos, jnp.int32)], norm, head, eps=eps,
                      quant=fp8)
                for x, pos in zip(xs, want)]
