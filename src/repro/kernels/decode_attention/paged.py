"""Paged decode attention: flash-decoding over physical KV pages.

The per-request page table and valid lengths ride in as **scalar-prefetch**
operands (pltpu.PrefetchScalarGridSpec), so the BlockSpec index map picks
each grid step's KV tile straight out of the arena — grid = (B, Hkv,
n_pages); program (b, h, j) DMAs physical page ``page_table[b, j]`` of KV
head ``h`` and folds it into an online softmax held in VMEM scratch for all
``group`` query heads that share that KV head, so each page is read once
per GQA group.  The output is written on the last page step.

Pages past a row's length (and, with a window, pages wholly before it)
are skipped: their index map is clamped onto the nearest live page, which
the pipeline does not fetch again, and their compute is predicated off.

Arena layout is the serving layout ``(num_pages, BLOCK, n_kv, D)``
(models/lm.py ``paged_arena_zeros``); the wrapper transposes to the
VMEM-friendly ``(num_pages, n_kv, BLOCK, D)`` tiling at the XLA level.
Logical slot ``j * BLOCK + t`` holds absolute position ``j * BLOCK + t``,
so one per-request valid length masks the unwritten tail of the last page
and every unallocated table entry at once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _live_pages(length, blk, window):
    """First and last logical page holding a position the query attends."""
    last = jnp.maximum(length - 1, 0) // blk
    if window is None:
        return 0, last
    return jnp.maximum(length - window, 0) // blk, last


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
                  acc_sc, *, scale, softcap, blk, window, n_pg):
    b, j = pl.program_id(0), pl.program_id(2)
    valid_len = len_ref[b]
    first, last = _live_pages(valid_len, blk, window)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when((valid_len > 0) & (j >= first) & (j <= last))
    def _page():
        q = q_ref[...].astype(jnp.float32) * scale                # (g, D)
        k = k_ref[...].astype(jnp.float32)                        # (blk, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < valid_len
        if window is not None:
            mask &= pos >= (valid_len - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]                                        # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_pg - 1)
    def _done():
        o_ref[...] = (acc_sc[...]
                      / jnp.maximum(l_sc[...], 1e-20)).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_arena, v_arena, page_table, lengths,
                                  *, window=None, softcap=None, scale=None,
                                  interpret=False):
    """q: (B, H, D); arenas: (P, BLOCK, Hkv, D); page_table: (B, n_pg)
    physical page per logical block; lengths: (B,) valid tokens (0 for a
    masked slot-pool row — its output is zeros the caller discards).
    Returns (B, H, D)."""
    B, H, D = q.shape
    P, blk, Hkv, _ = k_arena.shape
    group = H // Hkv
    n_pg = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    ka = k_arena.transpose(0, 2, 1, 3)                     # (P, Hkv, blk, D)
    va = v_arena.transpose(0, 2, 1, 3)
    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32)

    def kv_map(b, h, j, pt, ln):
        first, last = _live_pages(ln[b], blk, window)
        return (pt[b * n_pg + jnp.clip(j, first, last)], h, 0, 0)

    def q_map(b, h, j, pt, ln):
        return (b, h, 0, 0)

    kern = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                             blk=blk, window=window, n_pg=n_pg)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page table, lengths (SMEM)
        grid=(B, Hkv, n_pg),
        in_specs=[
            pl.BlockSpec((None, None, group, D), q_map),
            pl.BlockSpec((None, None, blk, D), kv_map),
            pl.BlockSpec((None, None, blk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, group, D), q_map),
        scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32),
                        pltpu.VMEM((group, 1), jnp.float32),
                        pltpu.VMEM((group, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32).reshape(-1),
      lengths.astype(jnp.int32), qg, ka, va)
    return out.reshape(B, H, D).astype(q.dtype)
