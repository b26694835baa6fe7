"""Pure-jnp oracle for split-K decode attention."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, lengths, *, window=None, softcap=None,
                         scale=None):
    """q: (B, H, D); k, v: (B, Hkv, S, D); lengths: (B,) -> (B, H, D)."""
    B, H, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(S)[None, None]
    mask = pos < lengths[:, None, None]
    if window is not None:
        mask &= pos >= (lengths[:, None, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p,
                      vr.astype(jnp.float32)).astype(q.dtype)


def paged_attention_ref(q, k_arena, v_arena, page_table, pos0, lengths, *,
                        window=None, softcap=None, scale=None):
    """Oracle for the paged kernel, in f32: gather each row's pages into
    a dense view at the XLA level and attend with a per-query causal
    mask.  q: (B, C, H, D) at positions ``pos0[b] + c``; arenas:
    (P, BLOCK, n_kv * D); page_table: (B, n_pg); lengths: (B,) (0 = dead
    row, zeros out)."""
    B, C, H, D = q.shape
    blk = k_arena.shape[1]
    n_kv = k_arena.shape[2] // D
    n_pg = page_table.shape[1]
    S = n_pg * blk
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    def dense(arena):                                   # (B, S, H, D)
        g = jnp.take(arena, page_table.reshape(-1), axis=0)
        g = g.reshape(B, S, n_kv, D).astype(jnp.float32)
        return jnp.repeat(g, H // n_kv, axis=2)

    s = jnp.einsum("bchd,bshd->bhcs", q.astype(jnp.float32),
                   dense(k_arena)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = (pos0[:, None] + jnp.arange(C)[None])[:, None, :, None]
    kpos = jnp.arange(S)[None, None, None]
    mask = (kpos <= qpos) & (kpos < lengths[:, None, None, None])
    if window is not None:
        mask &= kpos > qpos - window
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    o = jnp.einsum("bhcs,bshd->bchd", p, dense(v_arena))
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.astype(q.dtype)
