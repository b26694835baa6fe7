"""jit'd public wrapper for decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "n_splits", "impl"))
def decode_attention(q, k, v, lengths, *, window=None, softcap=None,
                     scale=None, n_splits=8, impl="auto"):
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return decode_attention_ref(q, k, v, lengths, window=window,
                                    softcap=softcap, scale=scale)
    return decode_attention_pallas(q, k, v, lengths, window=window,
                                   softcap=softcap, scale=scale,
                                   n_splits=n_splits,
                                   interpret=impl == "interpret")

