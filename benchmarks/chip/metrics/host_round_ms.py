"""Scheduler host work on the device's critical path: the median, over
the window's steps that dispatched a decode and no prefill, of the span
``sched.step`` less its ``sched.read_tokens`` (where the host waits for
the previous decode).  The device waits for the host in that time: the
next decode is dispatched only at its end.  The node's own spans, from
its step records."""
import statistics

import records


def read(run):
    got = records.window(run)
    if got is None:
        return None
    rounds = [r.end - r.start - r.spans.get("sched.read_tokens", 0.0)
              for r in got[1] if "sched.decode_dispatch" in r.spans
              and "engine.prefill_dispatch" not in r.spans]
    return statistics.median(rounds) * 1e3 if rounds else None
