"""The trace reduction on hand-made events: busy is the union of op
intervals inside the window, program time sums module events, and each
idle gap is named by the innermost host span open across it."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
from devtrace import Event  # noqa: E402

MS = 1e6


def _events():
    d = "device:0"
    return [
        Event("host", "python", "window", 0, 100 * MS),
        Event("host", "python", "step", 10 * MS, 50 * MS),
        Event("host", "python", "admission", 12 * MS, 8 * MS),
        Event("host", "python", "wait_arrival", 70 * MS, 25 * MS),
        # ops: [20, 30) and an overlapping [25, 40), then [60, 65)
        Event(d, "XLA Ops", "fusion.1", 20 * MS, 10 * MS),
        Event(d, "XLA Ops", "fusion.2", 25 * MS, 15 * MS),
        Event(d, "XLA Ops", "copy.3", 60 * MS, 5 * MS),
        # an op after the window is left out
        Event(d, "XLA Ops", "late", 120 * MS, 5 * MS),
        Event(d, "XLA Modules", "jit__prefill_paged_batched(7)",
              20 * MS, 20 * MS),
        Event(d, "XLA Modules", "jit__decode_paged_batched(9)",
              60 * MS, 5 * MS),
    ]


def test_busy_idle_and_programs():
    red = devtrace.reduce(_events())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.025)        # [20,40) + [60,65)
    assert devtrace.program_time(red, "_prefill_paged_batched") == \
        pytest.approx((0.02, 1))
    assert devtrace.program_time(red, "_decode_paged_batched") == \
        pytest.approx((0.005, 1))
    # gaps, longest first: [65,100) mid 82.5 -> wait_arrival; [0,20)
    # mid 10 -> step; [40,60) mid 50 -> step
    assert [n for n, _ in red.idle_gaps] == ["wait_arrival", "step", "step"]
    assert [s for _, s in red.idle_gaps] == pytest.approx([0.035, 0.02,
                                                           0.02])
    assert red.device_ops[0][0] == "jit__prefill_paged_batched"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce([e for e in _events() if e.name != "window"])
