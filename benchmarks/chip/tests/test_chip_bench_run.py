"""A whole run on the CPU at a tiny size, past the look for a chip: a
sound run is correct and the float8 control is not; each fault planted
in the timed path is caught, and a slower decode lowers the rate.  Also:
the window counts its last step whole, and without a TPU the command
exits non-zero and prints no result."""
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(ROOT / "src"))

import cells  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import tiny  # noqa: E402
import traffic  # noqa: E402

BENCH = cells.load_benchmark()
OFFLINE = "danube-coding-offline"
# the tiny configuration's own limit, set from CPU readings over four
# seeds: sound bf16 runs read widest gaps of 0 to 0.015, the float8
# control 0.077 to 0.17
LIMITS = {"logit_gap_max": {"limit": 0.04}}
SECONDS = 2.0


def measure(mix=tiny.BACKLOG, seed=3_000_000_019, control=False,
            plant=None):
    metrics = cells.reported(BENCH, OFFLINE, "end_to_end")
    return run.measure(tiny.SPEC, mix, seed, SECONDS, 0, jax.devices()[0],
                       LIMITS, metrics, tiny.PEAKS, check=False,
                       control=control, plant=plant)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         OFFLINE, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


@pytest.mark.parametrize("mix", [tiny.BACKLOG], ids=["backlog"])
def test_sound_run_is_correct(mix):
    res = measure(mix, control=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["tokens_checked"]["value"] >= 100
    assert list(res)[-1] == "checks"
    assert {"setup_s", "itl_p99_ms", "out_tok_per_s"} <= set(res["metrics"])
    # the control: the reference in float8 put in the program's place,
    # judged by the same rule, is not correct
    ctl = res["control"]
    assert ctl["correct"] is False, ctl
    gap = ctl["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"] == LIMITS["logit_gap_max"]["limit"]


def test_altered_token_is_caught():
    """A token altered where it is produced: the scheduler's output."""
    res = measure(plant=faults.altered_token)
    assert not res["correct"], res["checks"]


def test_wrong_page_table_is_caught():
    """A wrong KV read: every decode row reads its neighbour's pages."""
    res = measure(plant=faults.wrong_page_table)
    assert not res["correct"], res["checks"]


def test_slower_decode_lowers_the_rate():
    """Each decode dispatch takes SLOW_DECODE_S longer, many times a tiny
    step's time: the rate falls, and the tokens stay right."""
    sound = measure()["metrics"]["out_tok_per_s"]["value"]
    res = measure(plant=faults.slow_decode)
    assert res["correct"], res["checks"]
    rate = res["metrics"]["out_tok_per_s"]["value"]
    rows = tiny.SPEC["deployment"]["max_active"]
    assert 0 < rate < 0.7 * sound
    assert rate <= 2 * rows / faults.SLOW_DECODE_S


def _open_loop(mix, vocab, max_active, seed):
    """An open-loop kind for the test: a warm shared prefix, then a
    request due every 0.2 s that starts with it."""
    rng = np.random.default_rng(seed)
    head = traffic.tokens(rng, vocab, 64)
    timed = [(0.2 * i, traffic.Request(i, head + traffic.tokens(rng, vocab,
                                                                 20), 8))
             for i in range(8)]
    return traffic.Traffic(warm=[head], timed=timed)


def test_open_loop_traffic_is_served(monkeypatch):
    monkeypatch.setattr(traffic, "kind", lambda name: _open_loop)
    res = measure({"kind": "open-loop-test"})
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8


class _FakeScheduler:
    """A scheduler whose every step takes STEP_S and gives each of its
    rows one token."""
    STEP_S = 0.25

    def __init__(self, rows):
        self.slots = [SimpleNamespace(req=SimpleNamespace(req_id=i,
                                                          tokens=[1]),
                                      out=[], pos=0, cached_tokens=0)
                      for i in range(rows)]
        self.queue, self.active, self.done = [], [0], []
        self.metrics = {"decode_calls": 0}
        self.max_active = rows
        self.engine = SimpleNamespace(prefill_dispatches=0,
                                      prefill_tokens=0)

    def step(self):
        time.sleep(self.STEP_S)
        for s in self.slots:
            s.out.append(1)
        self.metrics["decode_calls"] += 1


def test_window_counts_its_last_step_whole():
    """Steps start at 0, 0.25, 0.5 and 0.75 s of a 0.9 s window: the last
    ends after the deadline and counts, and the rate divides by the time
    to its end."""
    sched = _FakeScheduler(rows=3)
    win = serve.run_window(sched, traffic.Traffic(), 0.9,
                           serve._Watch(sched))
    assert len(win.steps) == 4
    assert win.token_count() == 4 * 3
    assert win.elapsed >= 4 * _FakeScheduler.STEP_S > win.seconds
    rate = cells.reader("out_tok_per_s")(SimpleNamespace(win=win))
    assert rate == pytest.approx(12 / win.elapsed)
