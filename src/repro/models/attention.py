"""GQA attention: full-sequence (train/prefill), decode, ring-buffer SWA, cross.

The full-sequence path is *chunked over query blocks* (lax.scan) so the jnp
reference path lowered in the dry-run never materializes an (S, S) score
tensor — same O(S^2) FLOPs as flash attention with O(S * block_q) memory.
The paged serving attention (``paged_attention``: the decode step, the
prefill grid and the verify window) runs the Pallas paged kernel
(kernels/decode_attention/paged.py) on a TPU and a jnp gather path
elsewhere; the full-sequence and dense decode paths are jnp.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import constraints
from repro.models import common

NEG_INF = -2.0 ** 30  # large-but-finite: keeps softmax well-defined in bf16


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------

def init_attn(cfg, key, cross: bool = False):
    d, nq, nkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kq, kk, kv, ko = jax.random.split(key, 4)
    pd = cfg.params_dtype
    return {
        "wq": common.dense_init(kq, (d, nq, dh), d, pd),
        "wk": common.dense_init(kk, (d, nkv, dh), d, pd),
        "wv": common.dense_init(kv, (d, nkv, dh), d, pd),
        "wo": common.dense_init(ko, (nq, dh, d), nq * dh, pd),
    }


def _scale(cfg) -> float:
    return cfg.attn_scale if cfg.attn_scale else 1.0 / math.sqrt(cfg.d_head)


def _pad_heads_w(cfg, w, head_axis: int):
    """Zero-pad per GQA group so each group grows equally (preserves the
    original query-head -> kv-head assignment exactly)."""
    if not cfg.head_pad:
        return w
    nkv = cfg.n_kv_heads
    g = cfg.n_heads // nkv
    g_new = (cfg.n_heads + cfg.head_pad) // nkv
    shape = w.shape
    grouped = w.reshape(shape[:head_axis] + (nkv, g) + shape[head_axis + 1:])
    pad = [(0, 0)] * grouped.ndim
    pad[head_axis + 1] = (0, g_new - g)
    padded = jnp.pad(grouped, pad)
    return padded.reshape(shape[:head_axis] + (nkv * g_new,)
                          + shape[head_axis + 1:])


def q_heads(cfg) -> int:
    return cfg.n_heads + cfg.head_pad


def project_qkv(cfg, p, x, positions=None, rope: bool = True):
    dt = cfg.compute_dtype
    wq = _pad_heads_w(cfg, p["wq"].astype(dt), 1)
    q = jnp.einsum("bsd,dnh->bsnh", x, wq)
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"].astype(dt))
    if rope and cfg.rope_theta > 0 and positions is not None:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def project_kv_memory(cfg, p, mem):
    """Cross-attention K/V from a (B, T, d) memory (no RoPE)."""
    dt = cfg.compute_dtype
    k = jnp.einsum("btd,dnh->btnh", mem, p["wk"].astype(dt))
    v = jnp.einsum("btd,dnh->btnh", mem, p["wv"].astype(dt))
    return k, v


def out_proj(cfg, p, o):
    wo = _pad_heads_w(cfg, p["wo"].astype(cfg.compute_dtype), 0)
    return jnp.einsum("bsnh,nhd->bsd", o, wo)


# --------------------------------------------------------------------------
# Core blockwise attention
# --------------------------------------------------------------------------

def _expand_kv(k, n_heads):
    """(B, S, n_kv, h) -> (B, S, H, h) by repeating KV heads.

    Keeps the HEAD dim intact through the attention einsums so tensor
    parallelism shards it (reshaping H into (kv, group) factors breaks
    GSPMD head sharding — measured as replicated attention compute in the
    baseline; see EXPERIMENTS.md §Perf iteration 1)."""
    g = n_heads // k.shape[2]
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def _gqa_scores(q, k, scale, cap):
    """q: (B, Sq, H, h); k: (B, Skv, H, h) -> (B, H, Sq, Skv)."""
    s = jnp.einsum("bqhd,bthd->bhqt", q, k) * scale
    s = common.softcap(s.astype(jnp.float32), cap)
    return s


def _gqa_out(probs, v):
    """probs: (B, H, Sq, Skv); v: (B, Skv, H, h) -> (B, Sq, H, h)."""
    return jnp.einsum("bhqt,bthd->bqhd", probs, v)


def full_attention(cfg, q, k, v, q_positions, kv_positions,
                   causal: bool = True, window: Optional[int] = None,
                   block_q: int = 512):
    """Chunked full-sequence attention.

    q: (B, Sq, nq, h); k, v: (B, Skv, nkv, h).
    q_positions: (B, Sq) or (Sq,); kv_positions: (B, Skv) or (Skv,).
    """
    B, Sq, nq, h = q.shape
    scale, cap = _scale(cfg), cfg.attn_softcap
    k = _expand_kv(k, nq)
    v = _expand_kv(v, nq)
    if q_positions.ndim == 1:
        q_positions = jnp.broadcast_to(q_positions[None], (B, Sq))
    if kv_positions.ndim == 1:
        kv_positions = jnp.broadcast_to(kv_positions[None], (B, k.shape[1]))

    nblk = max(1, math.ceil(Sq / block_q))
    pad = nblk * block_q - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    qb_ = q.reshape(B, nblk, block_q, nq, h).transpose(1, 0, 2, 3, 4)
    qpos = q_positions.reshape(B, nblk, block_q).transpose(1, 0, 2)

    def body(carry, xs):
        qb, qp = xs                                   # (B, bq, H, h), (B, bq)
        s = _gqa_scores(qb, k, scale, cap)            # (B, H, bq, Skv) f32
        # pin scan residuals: batch on DP axes, heads on the TP axis —
        # GSPMD otherwise replicates the stacked softmax statistics that
        # the scan saves for backward (§Perf iteration 2)
        s = constraints.pin(s, ("batch", "model", None, None))
        m = jnp.ones((B, qp.shape[1], kv_positions.shape[1]), bool)
        if causal:
            m &= kv_positions[:, None, :] <= qp[:, :, None]
        if window is not None:
            m &= (qp[:, :, None] - kv_positions[:, None, :]) < window
        s = jnp.where(m[:, None, :, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        probs = constraints.pin(probs, ("batch", "model", None, None))
        ob = _gqa_out(probs, v)                       # (B, bq, H, h)
        return carry, constraints.pin(ob, ("batch", None, "model", None))

    _, ob = jax.lax.scan(body, (), (qb_, qpos))
    o = ob.transpose(1, 0, 2, 3, 4).reshape(B, nblk * block_q, nq, h)
    return o[:, :Sq]


# --------------------------------------------------------------------------
# Decode against caches
# --------------------------------------------------------------------------

def decode_attention(cfg, q, k_cache, v_cache, kv_positions, pos,
                     window: Optional[int] = None, active=None):
    """One-token decode.  q: (B, 1, nq, h); caches: (B, S, nkv, h);
    kv_positions: (B, S) absolute positions (-1 = empty); pos: (B,);
    active: optional (B,) bool — dead batch slots in a slot-pool decode get
    a fully-masked score row (uniform probs over finite NEG_INF, output
    discarded by the caller) instead of forcing a recompile per occupancy."""
    B, _, nq, h = q.shape
    scale, cap = _scale(cfg), cfg.attn_softcap
    kc = constraints.pin(_expand_kv(k_cache, nq),
                         ("batch", None, "model", None))
    vc = constraints.pin(_expand_kv(v_cache, nq),
                         ("batch", None, "model", None))
    s = _gqa_scores(q, kc, scale, cap)                # (B, H, 1, S)
    s = constraints.pin(s, ("batch", "model", None, None))
    valid = (kv_positions >= 0) & (kv_positions <= pos[:, None])
    if window is not None:
        valid &= (pos[:, None] - kv_positions) < window
    if active is not None:
        valid &= active[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
    return _gqa_out(probs, vc)                        # (B, 1, H, h)


def update_cache(k_cache, v_cache, kv_positions, k_new, v_new, slot):
    """Insert (B, 1, nkv, h) new K/V at per-batch ``slot`` (B,) int32."""
    B = k_cache.shape[0]
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, slot].set(k_new[:, 0])
    v_cache = v_cache.at[bidx, slot].set(v_new[:, 0])
    return k_cache, v_cache, kv_positions


# --------------------------------------------------------------------------
# Paged KV: block-granular arena indexed by per-request page tables
# --------------------------------------------------------------------------
#
# The arena is node-wide: one (num_pages, BLOCK, nkv * h) K and V slab per
# layer, so a page is one contiguous run that the paged kernel reads in
# place; a request's KV lives in the physical pages its page table names,
# so two requests sharing a prompt prefix alias the same pages instead of
# holding copies.  Physical page 0 is a scratch page (serving/page_pool):
# masked slot-pool rows scatter there and never read it back unmasked.
#
# ``impl`` picks the attention: "auto" is the Pallas kernel
# (kernels/decode_attention/paged.py) on a TPU and the jnp gather path
# elsewhere; "pallas", "interpret" (the kernel in Pallas's TPU interpreter)
# and "ref" (the jnp gather path) force a side.

def gather_pages(arena, page_table, n_kv):
    """arena: (P, BLOCK, nkv * h); page_table: (B, n_pg) int32 physical
    page per logical block -> (B, n_pg * BLOCK, nkv, h) dense per-request
    view.

    Logical position j of row b lives at arena[page_table[b, j // BLOCK],
    j % BLOCK]; unallocated table entries point at the scratch page and are
    masked by position in the attention that consumes the gather."""
    B, n_pg = page_table.shape
    _, blk, width = arena.shape
    g = jnp.take(arena, page_table.reshape(-1), axis=0)
    return g.reshape(B, n_pg * blk, n_kv, width // n_kv)


def write_page(k_arena, v_arena, k_new, v_new, page_table, pos, n_tok):
    """Write a (B, BLOCK, nkv, h) chunk that starts on a page boundary:
    row b's chunk fills the whole physical page ``page_table[b, pos[b] //
    BLOCK]``.  Dead rows (``n_tok`` 0) write the scratch page 0, so an
    empty grid row never corrupts a live page."""
    B, C = k_new.shape[:2]
    blk = k_arena.shape[1]
    phys = page_table[jnp.arange(B), pos // blk]          # (B,)
    if n_tok is not None:
        phys = jnp.where(n_tok > 0, phys, 0)
    k_arena = k_arena.at[phys].set(k_new.reshape(B, C, -1))
    v_arena = v_arena.at[phys].set(v_new.reshape(B, C, -1))
    return k_arena, v_arena


def write_tokens(k_arena, v_arena, k_new, v_new, page_table, pos, n_tok):
    """Write a (B, W, nkv, h) window token by token: token m of row b
    lands at physical page ``page_table[b, (pos[b]+m) // BLOCK]``, offset
    ``(pos[b]+m) % BLOCK``, so the window may straddle a page boundary.

    Tokens at or beyond ``n_tok[b]`` (ragged drafts; 0 for a dead row)
    write the scratch page 0, so pad and dead tokens never touch a live
    page, and the table lookup is clamped so a row parked near
    ``max_len`` cannot index past its page table."""
    B, W = k_new.shape[:2]
    blk = k_arena.shape[1]
    n_pg = page_table.shape[1]
    positions = pos[:, None] + jnp.arange(W)[None]            # (B, W)
    blocks = jnp.clip(positions // blk, 0, n_pg - 1)
    phys = jnp.take_along_axis(page_table, blocks, axis=1)    # (B, W)
    if n_tok is not None:
        phys = jnp.where(jnp.arange(W)[None] < n_tok[:, None], phys, 0)
    off = positions % blk
    k_arena = k_arena.at[phys, off].set(k_new.reshape(B, W, -1))
    v_arena = v_arena.at[phys, off].set(v_new.reshape(B, W, -1))
    return k_arena, v_arena


KV_WRITES = {"page": write_page, "tokens": write_tokens}


def paged_attention(cfg, q, k_arena, v_arena, page_table, pos0, n_tok,
                    window: Optional[int] = None, impl: str = "auto"):
    """Attention of a (B, W, nq, h) query window over paged KV whose own
    K/V is already in the arena.  Query m of row b sits at position
    ``pos0[b] + m`` and attends causally (and within ``window``) over its
    row's pages; ``n_tok`` (B,) counts each row's real tokens, 0 for a
    dead row, whose output is zero (None: every row is live).

    The kernel streams each row's live pages, below ``pos0 + W``, out of
    the arena.  The jnp path gathers each row's pages into a dense view:
    logical slot j holds position j, so the causal mask covers the
    unwritten tail of the last page and unallocated table entries."""
    B, W = q.shape[:2]
    live = None if n_tok is None else n_tok > 0
    use_kernel = (jax.default_backend() == "tpu" if impl == "auto"
                  else impl != "ref")
    if use_kernel:
        from repro.kernels.decode_attention.paged import \
            paged_attention_pallas
        lengths = pos0 + W
        if live is not None:
            lengths = jnp.where(live, lengths, 0)
        return paged_attention_pallas(
            q, k_arena, v_arena, page_table, pos0, lengths, window=window,
            softcap=cfg.attn_softcap or None, scale=_scale(cfg),
            interpret=impl == "interpret")
    S = page_table.shape[1] * k_arena.shape[1]
    kd = gather_pages(k_arena, page_table, cfg.n_kv_heads)
    vd = gather_pages(v_arena, page_table, cfg.n_kv_heads)
    kv_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q_positions = pos0[:, None] + jnp.arange(W)[None]
    o = full_attention(cfg, q, kd, vd, q_positions, kv_pos,
                       causal=True, window=window, block_q=W)
    if live is not None:
        o = jnp.where(live[:, None, None, None], o, 0.0)
    return o


def attn_layer_forward(cfg, p, x, positions, window=None, causal=True,
                       memory=None, block_q: int = 512):
    """Full-sequence layer: self-attention, or cross-attention if memory."""
    if memory is None:
        q, k, v = project_qkv(cfg, p, x, positions)
        kv_pos = positions
    else:
        dt = cfg.compute_dtype
        wq = _pad_heads_w(cfg, p["wq"].astype(dt), 1)
        q = jnp.einsum("bsd,dnh->bsnh", x, wq)
        if cfg.rope_theta > 0:
            q = common.apply_rope(q, positions, cfg.rope_theta)
        k, v = project_kv_memory(cfg, p, memory)
        T = memory.shape[1]
        kv_pos = jnp.arange(T)
        causal, window = False, None
    o = full_attention(cfg, q, k, v, positions, kv_pos,
                       causal=causal, window=window, block_q=block_q)
    return out_proj(cfg, p, o)
