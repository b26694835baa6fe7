"""Pallas kernel validation: interpret-mode vs pure-jnp oracles across
shape/dtype sweeps (the per-kernel allclose deliverable)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_hash.ops import chunk_hash_fixed
from repro.kernels.chunk_hash.ref import chunk_hash_ref
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mlstm.ops import mlstm_chunkwise

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,Hkv,S,D,causal,window,softcap,dtype", [
    (2, 4, 2, 256, 64, True, None, None, jnp.float32),
    (1, 4, 4, 256, 64, True, 64, None, jnp.float32),
    (2, 8, 2, 128, 64, True, None, 30.0, jnp.float32),
    (1, 2, 1, 256, 128, False, None, None, jnp.float32),
    (1, 4, 2, 128, 64, True, None, None, jnp.bfloat16),
])
def test_flash_attention(B, H, Hkv, S, D, causal, window, softcap, dtype):
    ks = jax.random.split(jax.random.fold_in(RNG, S + H + D), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    ref = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, impl="ref")
    pal = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, impl="interpret", bq=64, bk=64)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,Hkv,S,D,window,softcap,ns", [
    (2, 4, 2, 256, 64, None, None, 4),
    (2, 4, 1, 512, 64, None, None, 8),
    (1, 8, 8, 256, 128, 128, None, 5),
    (2, 2, 2, 256, 64, None, 50.0, 1),
])
def test_decode_attention(B, H, Hkv, S, D, window, softcap, ns):
    ks = jax.random.split(jax.random.fold_in(RNG, S + H + ns), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lengths = jax.random.randint(ks[3], (B,), S // 4, S + 1)
    ref = decode_attention(q, k, v, lengths, window=window, softcap=softcap,
                           impl="ref")
    pal = decode_attention(q, k, v, lengths, window=window, softcap=softcap,
                           n_splits=ns, impl="interpret")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,H,Hkv,D,blk,P,n_pg,window,softcap", [
    (2, 4, 2, 64, 32, 12, 4, None, None),
    (3, 4, 1, 64, 16, 9, 6, None, None),
    (1, 8, 8, 128, 32, 6, 3, 48, None),
    (2, 2, 2, 64, 32, 8, 4, None, 50.0),
    (2, 32, 8, 80, 32, 9, 4, None, None),       # h2o-danube-1.8b heads
    (2, 32, 8, 80, 32, 9, 4, 40, None),
])
def test_paged_decode_attention(B, H, Hkv, D, blk, P, n_pg, window,
                                softcap):
    """In-kernel page-table gather (scalar prefetch) vs the gather-then-
    dense oracle, including a zero-length (masked slot-pool) row."""
    ks = jax.random.split(jax.random.fold_in(RNG, P + n_pg + H), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    ka = jax.random.normal(ks[1], (P, blk, Hkv, D), jnp.float32)
    va = jax.random.normal(ks[2], (P, blk, Hkv, D), jnp.float32)
    pt = jax.random.randint(ks[3], (B, n_pg), 1, P)      # 0 = scratch page
    lengths = jnp.asarray([n_pg * blk - 3] + [0] * (B - 1), jnp.int32)
    ref = paged_decode_attention(q, ka, va, pt, lengths, window=window,
                                 softcap=softcap, impl="ref")
    pal = paged_decode_attention(q, ka, va, pt, lengths, window=window,
                                 softcap=softcap, impl="interpret")
    # rows with length 0 are fully masked garbage by contract (discarded
    # by the slot-pool caller) — compare only the live row
    np.testing.assert_allclose(np.asarray(pal[:1]), np.asarray(ref[:1]),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_matches_dense_decode():
    """Sequentially paged KV (identity page table) must reproduce the
    dense split-K kernel exactly — paging is layout, not math."""
    ks = jax.random.split(jax.random.fold_in(RNG, 77), 4)
    B, H, Hkv, D, blk, n_pg = 2, 4, 2, 64, 32, 4
    S = blk * n_pg
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    lengths = jnp.asarray([S, S // 2 + 5], jnp.int32)
    dense = decode_attention(q, k, v, lengths, impl="ref")
    # lay request b's KV out as pages [b*n_pg .. b*n_pg+n_pg)
    ka = k.transpose(0, 2, 1, 3).reshape(B * n_pg, blk, Hkv, D)
    va = v.transpose(0, 2, 1, 3).reshape(B * n_pg, blk, Hkv, D)
    pt = jnp.arange(B * n_pg, dtype=jnp.int32).reshape(B, n_pg)
    paged = paged_decode_attention(q, ka, va, pt, lengths,
                                   impl="interpret")
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,H,C,L,P,N", [
    (2, 2, 4, 16, 32, 8),
    (1, 4, 3, 32, 64, 16),
])
def test_mamba_scan(B, H, C, L, P, N):
    ks = jax.random.split(jax.random.fold_in(RNG, C * L + P), 5)
    xbar = jax.random.normal(ks[0], (B, H, C, L, P), jnp.float32) * 0.5
    loga = -jax.nn.softplus(jax.random.normal(ks[1], (B, H, C, L)))
    Bm = jax.random.normal(ks[2], (B, C, L, N), jnp.float32) * 0.5
    Cm = jax.random.normal(ks[3], (B, C, L, N), jnp.float32) * 0.5
    h0 = jax.random.normal(ks[4], (B, H, N, P), jnp.float32) * 0.1
    y_r, h_r = mamba_scan(xbar, loga, Bm, Cm, h0, impl="ref")
    y_p, h_p = mamba_scan(xbar, loga, Bm, Cm, h0, impl="interpret")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,C,L,dh", [
    (2, 2, 4, 16, 32),
    (1, 4, 2, 32, 64),
])
def test_mlstm_chunkwise(B, H, C, L, dh):
    ks = jax.random.split(jax.random.fold_in(RNG, C * L + dh), 5)
    q = jax.random.normal(ks[0], (B, H, C, L, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, C, L, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, C, L, dh), jnp.float32)
    gi = jax.random.normal(ks[3], (B, H, C, L)) * 2.0
    gf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, C, L)) + 4.0)
    h_r, (C_r, n_r, m_r) = mlstm_chunkwise(q, k, v, gi, gf, impl="ref",
                                           scale=0.17)
    h_p, (C_p, n_p, m_p) = mlstm_chunkwise(q, k, v, gi, gf,
                                           impl="interpret", scale=0.17)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(C_p), np.asarray(C_r),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_r),
                               rtol=1e-5, atol=1e-5)


def test_mlstm_state_continuation():
    B, H, C, L, dh = 1, 2, 2, 16, 32
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (B, H, 2 * C, L, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, 2 * C, L, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, 2 * C, L, dh), jnp.float32)
    gi = jax.random.normal(ks[3], (B, H, 2 * C, L)) * 2.0
    gf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, 2 * C, L)) + 4.0)
    h_full, _ = mlstm_chunkwise(q, k, v, gi, gf, impl="interpret")
    h1, st1 = mlstm_chunkwise(q[:, :, :C], k[:, :, :C], v[:, :, :C],
                              gi[:, :, :C], gf[:, :, :C], impl="interpret")
    h2, _ = mlstm_chunkwise(q[:, :, C:], k[:, :, C:], v[:, :, C:],
                            gi[:, :, C:], gf[:, :, C:], state0=st1,
                            impl="interpret")
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full[:, :, C:]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("width", [16, 64, 128])
def test_chunk_hash_matches_hrtree(width):
    toks = np.random.default_rng(0).integers(
        0, 50_000, (3, 512)).astype(np.int32)
    hp = np.asarray(chunk_hash_fixed(jnp.asarray(toks), width=width, bits=8,
                                     impl="interpret"))
    hr = chunk_hash_ref(toks, width=width, bits=8)
    assert np.array_equal(hp, hr)
