"""Architecture & shape configuration system.

Every assigned architecture is a frozen ``ArchConfig`` built from a repeating
``pattern`` of ``LayerSpec`` positions (scan-over-repeats keeps the HLO
compact for the 512-device dry-run).  ``reduced()`` returns a tiny same-family
config for CPU smoke tests.  ``input_specs()`` produces ShapeDtypeStruct
stand-ins for every model input of a given (config, shape) cell — no device
allocation, weak-type-correct, shardable.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# Layer / block specs
# --------------------------------------------------------------------------

MIXERS = ("attn", "cross_attn", "mamba", "mlstm", "slstm")
FFNS = ("dense", "moe", "none")


@dataclass(frozen=True)
class LayerSpec:
    """One position inside the repeating block pattern."""

    mixer: str = "attn"           # attn | cross_attn | mamba | mlstm | slstm
    window: Optional[int] = None  # sliding-window size for local attention
    ffn: str = "dense"            # dense | moe | none

    def __post_init__(self):
        assert self.mixer in MIXERS, self.mixer
        assert self.ffn in FFNS, self.ffn


@dataclass(frozen=True)
class MoESpec:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0           # shared (always-on) experts, Moonlight-style
    capacity_factor: float = 1.25  # E/k => lossless (no token drops)


@dataclass(frozen=True)
class MambaSpec:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    chunk: int = 128              # SSD chunk length (TPU-native form)


@dataclass(frozen=True)
class XLSTMSpec:
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 4.0 / 3.0
    conv_width: int = 4


# --------------------------------------------------------------------------
# Architecture config
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    d_head: Optional[int] = None  # default d_model // n_heads

    # attention options
    rope_theta: float = 10_000.0
    attn_softcap: Optional[float] = None    # gemma2: 50.0
    final_softcap: Optional[float] = None   # gemma2: 30.0
    attn_scale: Optional[float] = None      # override 1/sqrt(d_head)
    double_norm: bool = False               # gemma2 post-norms
    # zero-pad query heads per GQA group at compute time so the head dim
    # shards under TP (yi-34b: 56 -> 64).  Padded heads have zero output
    # projection — mathematically exact, ~n_pad/n_heads extra attention
    # FLOPs, 16x less replication.  §Perf iteration 3.
    head_pad: int = 0

    # ffn / embedding options
    act: str = "silu"             # silu (SwiGLU) | gelu (GeGLU or plain)
    glu: bool = True              # gated linear unit FFN
    norm: str = "rms"             # rms | ln
    tie_embeddings: bool = False
    embed_scale: bool = False     # gemma: scale embeddings by sqrt(d_model)

    moe: Optional[MoESpec] = None
    mamba: Optional[MambaSpec] = None
    xlstm: Optional[XLSTMSpec] = None

    # modality / enc-dec extras
    is_encdec: bool = False
    n_enc_layers: int = 0
    n_image_tokens: int = 0       # vlm cross-attention memory length (stub frontend)

    # long-context policy: None = derive (every mixer sub-quadratic);
    # hybrids override to True (their few full-attn layers shard KV over
    # the data axis — context parallelism)
    long_context_ok: Optional[bool] = None

    # numerics
    dtype: str = "bfloat16"       # activation/compute dtype
    param_dtype: str = "float32"  # master/storage dtype (serve path casts to dtype)

    # speculative decode (serving-time policy, not an architecture trait:
    # no effect on params/init).  With ``spec_enabled`` a paged scheduler
    # verifies up to ``spec_k`` self-drafted n-gram tokens per slot per
    # round in one multi-token dispatch (models/lm.verify_paged); outputs
    # stay token-identical to greedy non-speculative decoding.
    spec_enabled: bool = False
    spec_k: int = 4

    source: str = ""              # provenance note from the assignment brief

    # ---- derived ---------------------------------------------------------
    def __post_init__(self):
        assert self.n_layers % len(self.pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"pattern length {len(self.pattern)}")
        if self.d_head is None:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)

    @property
    def n_repeats(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        """Embedding/lm-head table size: vocab rounded up to a multiple of
        128 so the vocab dim shards under TP (whisper's 51865 -> 51968;
        all other assigned vocabs are already 128-aligned).  Logits beyond
        ``vocab`` are masked to -inf — outputs are exactly equivalent."""
        return (self.vocab + 127) // 128 * 128

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def params_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def d_inner_mamba(self) -> int:
        return self.mamba.expand * self.d_model if self.mamba else 0

    @property
    def supports_long_context(self) -> bool:
        """True iff decode state is sub-quadratic (O(1)/O(window) mixers),
        or the config explicitly opts in (hybrids: sparse full-attn layers
        with context-parallel KV)."""
        if self.long_context_ok is not None:
            return self.long_context_ok
        for spec in self.pattern:
            if spec.mixer == "attn" and spec.window is None:
                return False
        return True

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs are (or contain) decoders

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw = dict(
            n_layers=len(self.pattern),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_head=16,
            d_ff=128 if self.d_ff else 0,
            vocab=512,
            n_enc_layers=min(self.n_enc_layers, len(self.pattern)) if self.is_encdec else 0,
            n_image_tokens=16 if self.n_image_tokens else 0,
            dtype="float32",
            param_dtype="float32",
        )
        pattern = tuple(
            replace(s, window=8 if s.window is not None else None)
            for s in self.pattern)
        kw["pattern"] = pattern
        if self.moe:
            # lossless capacity so smoke tests are exactly reproducible
            kw["moe"] = MoESpec(num_experts=4, top_k=2, d_ff_expert=64,
                                num_shared=min(self.moe.num_shared, 1),
                                capacity_factor=2.0)
        if self.mamba:
            kw["mamba"] = MambaSpec(d_state=8, d_conv=4, expand=2, chunk=16)
        if self.xlstm:
            kw["xlstm"] = self.xlstm
        return replace(self, name=self.name + "-reduced", **kw)

    # Parameter count (dense + embeddings + experts), for MODEL_FLOPS.
    def param_counts(self) -> dict:
        d, dh = self.d_model, self.d_head
        nq, nkv = self.n_heads, self.n_kv_heads
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        per_pos_total = {}
        per_pos_active = {}
        for i, spec in enumerate(self.pattern):
            p = 0
            if spec.mixer in ("attn", "cross_attn"):
                p += d * nq * dh + 2 * d * nkv * dh + nq * dh * d
            elif spec.mixer == "mamba":
                di, ds = self.d_inner_mamba, self.mamba.d_state
                p += d * 2 * di + di * self.mamba.d_conv + di * 2 * ds
                p += di * ds + di + di * d  # dt/B/C proj + A + out
            elif spec.mixer == "mlstm":
                di = int(self.xlstm.proj_factor_mlstm * d)
                p += d * 2 * di + 2 * di * di + 2 * di + di * d
            elif spec.mixer == "slstm":
                nh = self.n_heads
                hdim = d // nh
                p += 4 * d * d + 4 * nh * hdim * hdim  # W gates + blockdiag R
                ff = int(self.xlstm.proj_factor_slstm * d)
                p += 3 * d * ff
            a = p
            if spec.ffn == "dense" and self.d_ff:
                ff = (3 if self.glu else 2) * d * self.d_ff
                p += ff
                a += ff
            elif spec.ffn == "moe":
                m = self.moe
                per_e = 3 * d * m.d_ff_expert
                p += m.num_experts * per_e + d * m.num_experts
                a += (m.top_k + m.num_shared) * per_e + d * m.num_experts
                p += m.num_shared * per_e
            per_pos_total[i] = p
            per_pos_active[i] = a
        total = emb + self.n_repeats * sum(per_pos_total.values())
        active = emb + self.n_repeats * sum(per_pos_active.values())
        if self.is_encdec:
            enc = self.n_enc_layers * (d * nq * dh * 2 + 2 * d * nkv * dh +
                                       (3 if self.glu else 2) * d * self.d_ff)
            total += enc
            active += enc
        return {"total": total, "active": active}


# --------------------------------------------------------------------------
# Shapes (assigned input-shape set for LM-family transformers)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def cell_is_runnable(cfg: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether an (arch x shape) cell applies; reason when it does not."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("full quadratic attention: 500k-token decode state is "
                       "O(seq) KV with O(seq) attention per token — skipped "
                       "per the brief (not sub-quadratic)")
    return True, ""


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   tokens/labels (B, S) int32   [+ frames/image embeddings]
    prefill: tokens (B, S) int32          [+ aux]
    decode:  token (B, 1) int32, pos (B,) int32 — the KV cache itself is part
             of the step signature and is built by ``models.lm.cache_specs``.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    cd = cfg.compute_dtype
    specs = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:  # decode: one new token against a cache of length S
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        specs["pos"] = jax.ShapeDtypeStruct((B,), i32)
    if cfg.is_encdec:
        # STUB modality frontend: precomputed conv frame embeddings.
        T = S // 2 if shape.kind != "decode" else cfg_enc_frames(cfg, S)
        if shape.kind == "decode":
            pass  # encoder output lives in the cross-KV cache
        else:
            specs["frames"] = jax.ShapeDtypeStruct((B, T, cfg.d_model), cd)
    if cfg.n_image_tokens:
        if shape.kind != "decode":
            specs["image_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.d_model), cd)
    return specs


def cfg_enc_frames(cfg: ArchConfig, seq_len: int) -> int:
    return seq_len // 2


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list:
    if not _REGISTRY:
        _load_all()
    return sorted(_REGISTRY)


_CONFIG_MODULES = [
    "xlstm_1p3b", "gemma2_9b", "yi_34b", "h2o_danube_1p8b", "granite_20b",
    "llama32_vision_11b", "moonshot_v1_16b_a3b", "dbrx_132b", "jamba_v0p1_52b",
    "whisper_base", "gentorrent_llama3_8b",
]


def _load_all():
    import importlib
    for m in _CONFIG_MODULES:
        importlib.import_module(f"repro.configs.{m}")


ASSIGNED = [
    "xlstm-1.3b", "gemma2-9b", "yi-34b", "h2o-danube-1.8b", "granite-20b",
    "llama-3.2-vision-11b", "moonshot-v1-16b-a3b", "dbrx-132b",
    "jamba-v0.1-52b", "whisper-base",
]
