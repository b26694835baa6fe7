"""Paged attention: one Pallas kernel for the decode step and the prefill
grid, reading each row's live pages where they lie in the arena.

Arena layout (models/lm.py ``paged_arena_zeros``): one ``(P, BLOCK,
n_kv * D)`` leaf per layer for K and one for V, so a page is one
contiguous ``BLOCK x n_kv * D`` run and the minor dimension is a whole
number of 128-lane tiles for every head width in use (640 for 8 x 80).
XLA keeps such an array row-major; a ``(.., n_kv, D)`` minor pair with
D = 80 would pad to 128 lanes, and the compiler then makes the page index
the minor dimension instead, which spreads every page over the whole slab.

Grid: one step per row.  Inside a row a loop walks the blocks of
``block_pages`` pages that hold a position some query of the row
attends (pages outside that range are neither fetched nor computed).  A
block's K and V pages are copied from HBM by the page table, which rides
in SMEM through scalar prefetch, into double-buffered VMEM while the
previous block is computed; the last block of a row starts the copy of
the next live row's first block.

All KV heads share one step.  The wrapper spreads each query over a
block-diagonal row of width ``n_kv * D`` (query head h's values sit in
the columns of its KV head), so ``q_bd @ K_blockᵀ`` gives every query's
scores against its own head only: the MXU does ``n_kv`` times the work,
the softmax statistics none extra.  The ``p @ V_block`` matmul and the
f32 accumulator it feeds are ``n_kv`` times wider too: each query row
keeps a ``(rows, n_kv * D)`` f32 sum, rescaled and rewritten every block,
whose own head's columns a 0/1 matmul picks out at the row's end.  Causal
masking is per query position (the chunk's query c sits at ``pos0 + c``),
and the window is masked too.  A row of length 0 (a dead slot-pool row or
a masked grid row) writes zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
S_TILE_BYTES = 1 << 20      # f32 score tile (query rows x block positions)
MAX_PAGES_PER_BLOCK = 16


def block_pages(rows, n_pg, blk):
    """Pages fetched per step: the most (up to 16, a power of two) whose
    f32 score tile stays within ``S_TILE_BYTES``, and no more than the
    table holds."""
    fit = max(1, S_TILE_BYTES // (rows * blk * 4))
    n = min(MAX_PAGES_PER_BLOCK, fit, n_pg)
    return 1 << (n.bit_length() - 1)


def _live_pages(pos0, length, blk, window):
    """First and last logical page holding a position some query of the
    row attends: queries sit at ``pos0 ..``, keys below ``length``."""
    last = jnp.maximum(length - 1, 0) // blk
    if window is None:
        return jnp.int32(0), last
    return jnp.maximum(pos0 - window + 1, 0) // blk, last


def _kernel(pt_ref, pos_ref, len_ref,                    # SMEM (prefetch)
            q_ref, qoff_ref, qkv_ref, ckv_ref, sel_ref,   # VMEM blocks
            k_hbm, v_hbm,                                 # HBM arenas
            o_ref,
            kbuf, vbuf, sem, slot_ref, m_sc, l_sc, acc_sc,
            *, n_rows, n_pg, npb, blk, window, softcap, scale):
    b = pl.program_id(0)

    def live(row):
        first, last = _live_pages(pos_ref[row], len_ref[row], blk, window)
        return first, jnp.minimum(last, n_pg - 1)

    def each_copy(row, j, slot, act):
        """``act`` on the K and V copy of every live page of block ``j``
        of ``row`` into ``slot``.  A slot whose page lies outside the live
        range is neither fetched nor waited on: it keeps an older page,
        whose positions the mask hides."""
        first, last = live(row)
        for i in range(npb):
            lp = j * npb + i

            @pl.when((lp >= first) & (lp <= last))
            def _():
                page = pt_ref[row * n_pg + lp]
                act(pltpu.make_async_copy(
                    k_hbm.at[page], kbuf.at[slot, i], sem.at[0, slot]))
                act(pltpu.make_async_copy(
                    v_hbm.at[page], vbuf.at[slot, i], sem.at[1, slot]))

    def start(row, j, slot):
        each_copy(row, j, slot, lambda c: c.start())

    def next_live_row():
        return jax.lax.fori_loop(
            b + 1, n_rows,
            lambda r, nxt: jnp.where((nxt == n_rows) & (len_ref[r] > 0),
                                     r, nxt),
            jnp.int32(n_rows))

    @pl.when(b == 0)
    def _first_fetch():
        slot_ref[0] = 0
        # a slot never fetched holds no page yet; the masked p is 0 there,
        # and 0 x V must not meet a NaN left in fresh VMEM
        vbuf[...] = jnp.zeros_like(vbuf)
        row = jax.lax.fori_loop(
            0, n_rows,
            lambda r, nxt: jnp.where((nxt == n_rows) & (len_ref[r] > 0),
                                     r, nxt),
            jnp.int32(n_rows))

        @pl.when(row < n_rows)
        def _():
            start(row, live(row)[0] // npb, 0)

    length = len_ref[b]

    @pl.when(length == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _row():
        first, last = live(b)
        j0, j1 = first // npb, last // npb
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        q = q_ref[...]                                   # (rows, n_kv*D)
        qpos = pos_ref[b] + qoff_ref[...]                # (rows, 1)

        def block(j, carry):
            slot = slot_ref[0]

            @pl.when(j < j1)
            def _():
                start(b, j + 1, 1 - slot)

            @pl.when(j == j1)
            def _():
                nxt = next_live_row()

                @pl.when(nxt < n_rows)
                def _():
                    start(nxt, live(nxt)[0] // npb, 1 - slot)

            each_copy(b, j, slot, lambda c: c.wait())
            k = kbuf[slot].reshape(npb * blk, -1)         # (T, n_kv*D)
            v = vbuf[slot].reshape(npb * blk, -1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = j * (npb * blk) + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = (kpos <= qpos) & (kpos < length)
            if window is not None:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
            acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[...] = m_new
            slot_ref[0] = 1 - slot
            return carry

        jax.lax.fori_loop(j0, j1 + 1, block, 0)
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        # keep each query's own KV head's columns, then fold the n_kv
        # column groups onto D by a 0/1 matmul: one nonzero term per
        # output, so the bf16 result is exact
        own = qkv_ref[...] == ckv_ref[...]               # (rows, n_kv*D)
        out = jnp.where(own, out, 0.0).astype(sel_ref.dtype)
        o_ref[...] = jax.lax.dot_general(
            out, sel_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def paged_attention_pallas(q, k_arena, v_arena, page_table, pos0, lengths,
                           *, window=None, softcap=None, scale=None,
                           interpret=False):
    """q: (B, C, H, D) queries at positions ``pos0[b] + c``; arenas:
    (P, BLOCK, n_kv * D) in HBM; page_table: (B, n_pg) physical page per
    logical block; pos0: (B,); lengths: (B,) keys ``[0, length)`` exist
    (0 for a dead row, whose output is zeros).  Query c attends keys at
    positions ``<= pos0 + c`` (and ``> pos0 + c - window``).  Returns
    (B, C, H, D) in q's dtype; scores, softmax statistics and sums are
    f32."""
    B, C, H, D = q.shape
    P, blk, width = k_arena.shape
    n_kv = width // D
    group = H // n_kv
    n_pg = page_table.shape[1]
    rows = H * C
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    npb = block_pages(rows, n_pg, blk)

    # rows ordered (query head, chunk offset); row r's KV head is
    # r // (group * C), its offset r % C
    q_kv = np.repeat(np.arange(n_kv), group * C).astype(np.int32)
    q_off = np.tile(np.arange(C), H).astype(np.int32)
    col_kv = np.repeat(np.arange(n_kv), D).astype(np.int32)
    qr = q.transpose(0, 2, 1, 3).reshape(B, rows, D)
    own = jnp.asarray(q_kv[:, None] == np.arange(n_kv)[None])  # (rows, n_kv)
    q_bd = jnp.where(own[None, :, :, None], qr[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(B, rows, width)
    sel = jnp.asarray(np.tile(np.eye(D), (n_kv, 1)), q.dtype)  # (width, D)
    n_len = jnp.minimum(lengths.astype(jnp.int32), n_pg * blk)

    kern = functools.partial(_kernel, n_rows=B, n_pg=n_pg, npb=npb,
                             blk=blk, window=window, softcap=softcap,
                             scale=scale)
    whole = lambda b, *_: (0, 0)                       # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # page table, pos0, lengths
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, rows, width), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((rows, 1), whole),
            pl.BlockSpec((rows, 1), whole),
            pl.BlockSpec((1, width), whole),
            pl.BlockSpec((width, D), whole),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, rows, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, npb, blk, width), k_arena.dtype),
            pltpu.VMEM((2, npb, blk, width), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        # the TPU interpreter runs each copy at its wait and fills fresh
        # VMEM with NaN, so a copy waited on wrongly or a slot read before
        # it is filled shows in the result
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",     # the kernel's name in a device trace
    )(page_table.astype(jnp.int32).reshape(-1), pos0.astype(jnp.int32),
      n_len, q_bd, jnp.asarray(q_off[:, None]), jnp.asarray(q_kv[:, None]),
      jnp.asarray(col_kv[None]), sel, k_arena, v_arena)
    return out.reshape(B, H, C, D).transpose(0, 2, 1, 3)

