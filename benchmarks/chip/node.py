"""The system under test: one model node's served path, built from a
configuration file as a node builds it.

``RealEngine`` over a paged KV arena, driven by the continuous-batching
``Scheduler``.  The configuration file gives the widths; implementation
options (``head_pad``, ``use_kernels``, ``spec_enabled``) are the
program's own, so a PR that changes them is measured.
"""
from __future__ import annotations

import dataclasses

import jax

from weights import make_params

# configuration-file key -> ArchConfig field, for the widths that must
# equal the program's registered configuration
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
          "vocab_size": "vocab", "num_hidden_layers": "n_layers",
          "rope_theta": "rope_theta"}


def arch_config(spec: dict, check: bool = True):
    """The program's ``ArchConfig`` for ``spec``.  With ``check``, every
    width and the window must equal the registered configuration, apart
    from the keys ``spec`` lists as reduced."""
    from repro.configs.base import get_config
    reg = get_config(spec["program_config"])
    if reg.family != "dense" or any(s.mixer != "attn" or s.ffn != "dense"
                                    for s in reg.pattern):
        raise ValueError(f"{reg.name}: not a dense attention decoder")
    if (spec["hidden_act"], reg.act, reg.glu, reg.norm) != \
            ("silu", "silu", True, "rms") or \
            spec["tie_word_embeddings"] != reg.tie_embeddings:
        raise ValueError(f"{reg.name}: not a SwiGLU/RMSNorm decoder with "
                         f"the configuration's head")
    window = spec.get("sliding_window")
    if check:
        diff = {k: (spec[k], getattr(reg, f)) for k, f in WIDTHS.items()
                if k not in spec["reduced"] and spec[k] != getattr(reg, f)}
        if {s.window for s in reg.pattern} != {window}:
            diff["sliding_window"] = (window, reg.pattern[0].window)
        if diff:
            raise ValueError(f"{spec['name']} differs from the program's "
                             f"{reg.name}: {diff}")
    dtype = spec["torch_dtype"]
    return dataclasses.replace(
        reg, pattern=tuple(dataclasses.replace(s, window=window)
                           for s in reg.pattern),
        dtype=dtype, param_dtype=dtype,
        **{f: spec[k] for k, f in WIDTHS.items()})


def program_params(canon: dict, model):
    """The benchmark's weights in the program's parameter tree; the tree
    and every shape must be what ``model.init`` makes."""
    lay = canon["layers"]
    params = {
        "embed": canon["embed"],
        "final_norm": {"scale": canon["final_norm"]},
        "lm_head": canon["lm_head"],
        "blocks": ({"norm1": {"scale": lay["attn_norm"]},
                    "mixer": {n: lay[n] for n in ("wq", "wk", "wv", "wo")},
                    "norm2": {"scale": lay["mlp_norm"]},
                    "ffn": {n: lay[n] for n in ("w_gate", "w_up",
                                                "w_down")}},),
    }
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if got != want:
        raise ValueError(f"the program's parameter tree changed:\n"
                         f"want {want}\ngot {got}")
    return params


def build(spec: dict, seed: int, device, check: bool = True):
    """(engine, scheduler) of one node on ``device``, weights from
    ``seed``."""
    from repro.models.lm import build_model
    from repro.serving.engine import RealEngine
    from repro.serving.scheduler import Scheduler
    cfg = arch_config(spec, check)
    model = build_model(cfg)
    params = program_params(make_params(spec, seed, device), model)
    dep = spec["deployment"]
    pages = dep["arena_pages"]
    # page_bytes is only known once the engine has built its arena
    eng = RealEngine(cfg, model, params, max_len=dep["max_len"],
                     num_pages=pages)
    eng.prefix_cache.max_bytes = dep["prefix_cache_pages"] * eng.page_bytes
    return eng, Scheduler(eng, max_active=dep["max_active"])
