"""Gradient compression for the DP all-reduce path: int8 quantization with
error feedback (residual carry), plus top-k sparsification — and the KV
page wire codec the overlay's cross-node page migration rides on.

In a real multi-pod deployment the inter-pod (DCN) all-reduce runs on the
int8 payload (32x less traffic than f32 at equal step count); here the
transform is applied to the gradient pytree inside train_step so its
*numerics* (and the error-feedback convergence behaviour) are exactly what
the cluster would see.  tests/test_compression.py checks the quantization
error bound and that error feedback keeps SGD convergent.

``compress_kv_blocks``/``decompress_kv_blocks`` serialize a gathered
(R, n_pages, BLOCK, nkv * h) K/V slab for the ``kv_pages`` overlay message
(serving/engine.export_pages -> import_pages): ``raw`` ships the arena
dtype losslessly, ``fp16`` halves f32 wire bytes, ``int8`` quantizes with
a per-(repeat, page) scale — the same max-abs scheme as the gradient path,
minus error feedback (pages are shipped once, there is no residual to
carry).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _quantize_int8(g):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def init_error_state(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_int8_ef(grads, err_state):
    """int8 + error feedback.  Returns (grads_as_transmitted, new_err)."""
    def one(g, e):
        g = g.astype(jnp.float32) + e
        q, s = _quantize_int8(g)
        deq = _dequantize(q, s)
        return deq, g - deq

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_e = jax.tree.unflatten(treedef, [o[1] for o in out])
    return new_g, new_e


def compress_topk_ef(grads, err_state, frac: float = 0.05):
    """Magnitude top-k sparsification with error feedback."""
    def one(g, e):
        g = g.astype(jnp.float32) + e
        flat = g.reshape(-1)
        k = max(1, int(flat.size * frac))
        thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        mask = (jnp.abs(g) >= thresh).astype(jnp.float32)
        sent = g * mask
        return sent, g - sent

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, reaching into ml_dtypes for the jax-only floats
    (bfloat16 arenas serialize through their ml_dtypes view)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def compress_kv_blocks(blocks, mode: str = "fp16") -> dict:
    """(R, n_pages, BLOCK, nkv * h) K/V slab -> msgpack-able wire record.

    ``raw`` is lossless (arena dtype bytes as-is); ``fp16`` casts float32
    arenas down for half the wire bytes; ``int8`` quantizes with one
    max-abs scale per (repeat, page) so a hot page with outliers never
    flattens its neighbours' resolution."""
    arr = np.asarray(jax.device_get(blocks))
    rec = {"mode": mode, "dtype": str(arr.dtype), "shape": list(arr.shape)}
    if mode == "raw":
        rec["data"] = arr.tobytes()
    elif mode == "fp16":
        rec["data"] = arr.astype(np.float16).tobytes()
    elif mode == "int8":
        flat = arr.astype(np.float32).reshape(arr.shape[0], arr.shape[1], -1)
        scale = np.maximum(np.abs(flat).max(axis=-1), 1e-12) / 127.0
        q = np.clip(np.round(flat / scale[..., None]), -127, 127)
        rec["data"] = q.astype(np.int8).tobytes()
        rec["scale"] = scale.astype(np.float32).tobytes()
    else:
        raise ValueError(f"unknown KV wire mode {mode!r}")
    return rec


def decompress_kv_blocks(rec: dict, dtype=None):
    """Wire record -> (R, n_pages, BLOCK, nkv * h) ndarray in ``dtype``
    (defaults to the source arena dtype recorded at compression)."""
    shape = tuple(int(s) for s in rec["shape"])
    out_dtype = _np_dtype(str(dtype)) if dtype is not None \
        else _np_dtype(rec["dtype"])
    mode = rec["mode"]
    if mode == "raw":
        arr = np.frombuffer(rec["data"], _np_dtype(rec["dtype"]))
        arr = arr.reshape(shape)
    elif mode == "fp16":
        arr = np.frombuffer(rec["data"], np.float16).reshape(shape)
    elif mode == "int8":
        q = np.frombuffer(rec["data"], np.int8)
        q = q.reshape(shape[0], shape[1], -1).astype(np.float32)
        scale = np.frombuffer(rec["scale"], np.float32)
        scale = scale.reshape(shape[0], shape[1])
        arr = (q * scale[..., None]).reshape(shape)
    else:
        raise ValueError(f"unknown KV wire mode {mode!r}")
    return np.asarray(arr, out_dtype)


def compression_ratio_int8(params) -> float:
    """Wire-bytes ratio vs f32 all-reduce (scale overhead included)."""
    total_f32 = sum(4 * p.size for p in jax.tree.leaves(params))
    total_int8 = sum(p.size + 4 for p in jax.tree.leaves(params))
    return total_f32 / total_int8
