"""The weights and the reference over a configuration's block family.

danube's family reproduces, bit for bit, the leaves and the reference
logits that the harness made before it named families (digests pinned
from that code at one seed).  A family of two kinds of layer, local to
this test, runs through the lookup by name, the weights and the
reference with no edit to the harness; an unknown family is an error
that names ``families/``."""
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import arith  # noqa: E402
import cells  # noqa: E402
import reference  # noqa: E402
import tiny  # noqa: E402
import weights  # noqa: E402

SEED = 3_000_000_019        # over 32 bits: both halves of the key
LEAF_DIGESTS = {
    "attn_norm": "63fc32ff27c311a6", "mlp_norm": "01ee05cf0d76cd3a",
    "w_down": "bffc661c895873e4", "w_gate": "b9769aaabd28038b",
    "w_up": "90e2bc1d510033bb", "wk": "bd2fb34ddd451870",
    "wo": "2fad8869a953f7bd", "wq": "337dfc06bd6392e6",
    "wv": "ce869cfe8112bc74", "embed": "2e46c74cb69e33c3",
    "final_norm": "73ca5979928cafff", "lm_head": "8046c15a6d828658",
}
# XLA's CPU backend sums in another order when it has one thread: each
# entry holds both readings of the same code
LOGIT_DIGESTS = {
    None: ({"73a40a521a6bc803", "0abd3046b38fe651"},
           {"6411476ae55614ba", "8b3989b7cf4745a5"}),
    "fp8": ({"5013476ab5729aaa"},
            {"f6ce4808a405212c", "5c1581a5fd5a18e7"}),
}
TWO_KIND = {"name": "twokind-tiny", "family": "twokind", "hidden_size": 32,
            "intermediate_size": 64, "num_hidden_layers": 3,
            "vocab_size": 128, "rms_norm_eps": 1e-5}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def _pin_sequences():
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 512, 300).tolist(),
            rng.integers(1, 512, 512).tolist()]
    return seqs, [np.array([0, 7, 150, 299]), np.array([5, 255, 511])]


def test_llama_weights_are_pinned():
    canon = weights.make_params(tiny.SPEC, SEED, jax.devices()[0])
    layers, = canon["layers"]
    got = {n: digest(v) for n, v in layers.items()}
    got.update({n: digest(canon[n]) for n in weights.TOP_LEAVES})
    assert got == LEAF_DIGESTS
    maker = weights.LeafMaker(tiny.SPEC, SEED)
    one = [maker.layer("block", i) for i in range(2)]
    got = {n: digest(jnp.stack([w[n] for w in one])) for n in one[0]}
    got.update({n: digest(maker.top(n)) for n in weights.TOP_LEAVES})
    assert got == LEAF_DIGESTS


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_llama_reference_logits_are_pinned(quant):
    seqs, want = _pin_sequences()
    got = reference.logits_at(tiny.SPEC, SEED, seqs, want, 512, quant=quant)
    for g, pinned in zip(got, LOGIT_DIGESTS[quant]):
        assert digest(np.asarray(g, np.float32)) in pinned


@pytest.fixture
def two_kind(monkeypatch):
    monkeypatch.setattr(cells, "FAMILIES", HERE / "tests" / "families")
    return cells.family("twokind")


def test_two_kind_weights_follow_the_groups(two_kind):
    canon = weights.make_params(TWO_KIND, SEED, jax.devices()[0])
    wide, mix = canon["layers"]
    assert set(wide) == {"norm", "w_in", "w_out"}
    assert set(mix) == {"norm", "w_mix"}
    assert wide["w_in"].shape == (1, 32, 64) and mix["w_mix"].shape[0] == 2
    maker = weights.LeafMaker(TWO_KIND, SEED)
    for (kind, layers), stacked in zip(weights.layer_groups(TWO_KIND),
                                       canon["layers"]):
        for j, i in enumerate(layers):
            one = maker.layer(kind, i)
            for n in stacked:
                assert jnp.array_equal(stacked[n][j], one[n]), (kind, i, n)
    # layers are numbered over every group: each layer's leaves differ
    norms = [wide["norm"][0], mix["norm"][0], mix["norm"][1]]
    assert len({digest(n) for n in norms}) == 3
    assert arith.matmul_params(TWO_KIND) == \
        two_kind.matmul_params(TWO_KIND)


def test_two_kind_reference_walks_the_groups_in_order(two_kind, monkeypatch):
    maker = weights.LeafMaker(TWO_KIND, SEED)
    layers = [("wide", maker.layer("wide", 0)), ("mix", maker.layer("mix", 1)),
              ("mix", maker.layer("mix", 2))]
    calls = []
    layer = two_kind.layer

    def recorded(kind, w, x, spec, quant):
        calls.append((kind, {n: digest(v) for n, v in w.items()}))
        return layer(kind, w, x, spec, quant)
    monkeypatch.setattr(two_kind, "layer", recorded)
    toks = np.random.default_rng(1).integers(1, 128, 100).tolist()
    pos = np.array([0, 50, 99])
    got, = reference.logits_at(TWO_KIND, SEED, [toks], [pos], 512)
    assert calls == [(k, {n: digest(v) for n, v in w.items()})
                     for k, w in layers]
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(toks + [0] * (512 - len(toks)), jnp.int32)
        x = jnp.take(maker.top("embed"), ids, axis=0).astype(jnp.float32)
        for kind, w in layers:
            x = layer(kind, w, x, TWO_KIND, False)
        want = reference._head(x[pos], maker.top("final_norm"),
                               maker.top("lm_head"), eps=1e-5, quant=False)
    assert jnp.array_equal(got, want)


def test_unknown_family_names_the_directory():
    with pytest.raises(ValueError, match="families/"):
        cells.family("no-such-family")
