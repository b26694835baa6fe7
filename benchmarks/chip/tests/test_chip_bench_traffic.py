"""The traffic generator: deterministic in the seed, the same sizes and
arrivals for every seed, the stated length statistics, and each mix's
kind found by name."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import traffic  # noqa: E402

CODING = cells.traffic("coding-backlog")
BIG = 3_000_000_017


def _sizes(t):
    return Counter((len(r.tokens), r.max_new) for r in t.backlog + t.in_flight)


def test_backlog_deterministic_and_same_sizes_per_seed():
    a = traffic.generate(CODING, 32000, 16, BIG)
    b = traffic.generate(CODING, 32000, 16, BIG)
    c = traffic.generate(CODING, 32000, 16, 7)
    assert [r.tokens for r in a.backlog] == [r.tokens for r in b.backlog]
    assert [r.tokens for r in a.in_flight] == [r.tokens for r in b.in_flight]
    assert [r.tokens for r in a.backlog] != [r.tokens for r in c.backlog]
    # the same prompt lengths and budgets: the seed does not change the work
    assert _sizes(a) == _sizes(c)
    assert [len(r.tokens) for r in a.backlog] == \
        [len(r.tokens) for r in c.backlog]


def test_backlog_length_statistics():
    t = traffic.generate(CODING, 32000, 16, 1)
    assert len(t.in_flight) == 16 and len(t.backlog) == CODING["requests"]
    prompts = np.array([len(r.tokens) for r in t.backlog])
    # prefix ~200 plus suffix ~1,600 (+-30%), clipped to fit the context
    assert 1700 < prompts.mean() < 1900
    assert 0.2 < prompts.std() / 1800 < 0.35
    assert all(r.max_new == 1000 for r in t.backlog)
    assert all(len(r.tokens) + r.max_new <= 4096
               for r in t.backlog + t.in_flight)
    # in flight: residual budgets spread evenly over (0, 1000]
    resid = sorted(r.max_new for r in t.in_flight)
    assert resid == [round(1000 * (i + 0.5) / 16) for i in range(16)]
    # prefixes are shared: Zipf 0.8 over 512 prefixes repeats some
    heads = Counter(tuple(r.tokens[:8]) for r in t.backlog)
    assert max(heads.values()) >= 3


def test_kind_found_by_name():
    assert callable(traffic.kind(CODING["kind"]))
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.generate(dict(CODING, kind="no-such-kind"), 32000, 16, 1)

