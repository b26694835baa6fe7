"""The trace reduction on an excerpt of a trace recorded on a TPU v5e:
busy time against an independent count on a microsecond grid, program
time against the module events, and the longest gap named by the host
span open across it."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402

DATA = json.loads((Path(__file__).parent / "data"
                   / "trace_v5e_excerpt.json").read_text())


def _events():
    lo, hi = DATA["window_ns"]
    ev = [devtrace.Event(*row) for row in DATA["events"]]
    return ev + [devtrace.Event("host", "python3", "window", lo, hi - lo)]


def test_busy_against_a_microsecond_grid():
    lo, hi = DATA["window_ns"]
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for plane, line, _, start, dur in DATA["events"]:
        if line == "XLA Ops" and lo <= start < hi:
            grid[start // 1000:min(hi, start + dur) // 1000] = True
    red = devtrace.reduce(_events())
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # the grid rounds each interval's ends to a microsecond
    assert red.busy_s == pytest.approx(grid.sum() * 1e-6, abs=2e-5)
    assert 0.9 < red.busy_s / red.window_s < 1.0


def test_program_time_and_gaps():
    red = devtrace.reduce(_events())
    decode = [dur for _, line, name, _, dur in DATA["events"]
              if line == "XLA Modules" and "_decode_paged_batched" in name]
    secs, n = devtrace.program_time(red, "_decode_paged_batched")
    assert n == len(decode) == 2
    assert secs == pytest.approx(sum(decode) * 1e-9)
    # the device's decode step at 16 rows, as recorded
    assert 0.1 < secs / n < 0.2
    assert devtrace.program_time(red, "_prefill_paged_batched")[1] == 1
    name, longest = red.idle_gaps[0]
    assert longest == max(s for _, s in red.idle_gaps)
    # the longest gap in the excerpt falls while a slot is retired
    assert name == "finish" and 0.005 < longest < 0.01
