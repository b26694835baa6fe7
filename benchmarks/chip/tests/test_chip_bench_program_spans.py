"""The readers of the program's own step records (``records.py``) on a
whole traced run on the CPU at a tiny size: each reads a number, and
``prefill_fill_pct`` is the hand count of the window's prefill grid.
Without the program's recorder, or with a ring too short for the window,
each reads nothing."""
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(ROOT / "src"))

import cells  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

from repro.serving import trace  # noqa: E402
from repro.serving.prefix_cache import BLOCK  # noqa: E402

BENCH = cells.load_benchmark()
OFFLINE = "danube-coding-offline"
NEW = ("prefill_fill_pct", "host_round_ms", "kv_pages_used_pct")
LIMITS = {"logit_gap_max": {"limit": 0.04}}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced tiny run of the offline backlog: its result, and the
    ``Run`` its readers saw."""
    seen = []
    real = cells.reader

    def spying(name):
        fn = real(name)

        def read(r):
            seen.append(r)
            return fn(r)
        return read
    cells.reader = spying
    try:
        res = run.measure(tiny.SPEC, tiny.BACKLOG, 3_000_000_021, 2.0, 1,
                          jax.devices()[0], LIMITS,
                          cells.reported(BENCH, OFFLINE, "per_layer"),
                          tiny.PEAKS, check=False)
    finally:
        cells.reader = real
    return res, seen[0]


def test_readers_read_numbers(traced):
    res, r = traced
    assert res["correct"], res["checks"]
    got = {n: res["metrics"][n]["value"] for n in NEW}
    assert 0 < got["prefill_fill_pct"] <= 100
    assert got["host_round_ms"] > 0
    assert 0 < got["kv_pages_used_pct"] <= 100
    assert r.win.prefill_dispatches > 0


def test_prefill_fill_is_the_hand_count(traced):
    res, r = traced
    rows = tiny.SPEC["deployment"]["max_active"]
    hand = 100.0 * r.win.prefill_tokens / (r.win.prefill_dispatches
                                           * rows * BLOCK)
    assert res["metrics"]["prefill_fill_pct"]["value"] == \
        pytest.approx(hand, rel=1e-12)


def _run(n_steps):
    return SimpleNamespace(win=SimpleNamespace(steps=[None] * n_steps))


def _recorder(n_steps):
    """A live recorder whose last ``n_steps`` step records read
    numbers."""
    rec = trace.Recorder()
    for i in range(n_steps):
        with rec.step(lambda: {"prefill_tokens": i, "prefill_slots": i,
                               "pages_used": 1, "pages_total": 2}):
            with rec.span("sched.decode_dispatch"):
                pass
    return rec


def test_no_recorder_reads_nothing(monkeypatch):
    """The parent's case: ``repro.serving`` has no module ``trace``.  A
    live recorder with records to read shows that the import failed."""
    import repro.serving
    rec = _recorder(6)
    assert trace.latest() is rec
    assert cells.reader("prefill_fill_pct")(_run(3)) == 100.0
    monkeypatch.delattr(repro.serving, "trace")
    monkeypatch.setitem(sys.modules, "repro.serving.trace", None)
    for name in NEW:
        assert cells.reader(name)(_run(3)) is None


def test_ring_without_the_record_before_the_window_reads_nothing(
        monkeypatch):
    monkeypatch.setattr(trace, "STEP_CAPACITY", 4)
    rec = _recorder(6)
    assert trace.latest() is rec and len(rec.steps) == 4
    for name in NEW:
        assert cells.reader(name)(_run(4)) is None
    read = {name: cells.reader(name)(_run(3)) for name in NEW}
    assert read["prefill_fill_pct"] == 100.0
    assert read["kv_pages_used_pct"] == 50.0
    assert read["host_round_ms"] >= 0
