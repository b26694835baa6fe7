"""Admission prefill (``engine.prefill_requests``): real prompt tokens
over the grid's token slots (rows x BLOCK of every prefill dispatch) in
the window, in percent.  The node's counters ``prefill_tokens`` and
``prefill_slots``, from its step records."""
import records


def read(run):
    got = records.window(run)
    if got is None:
        return None
    before, steps = got
    c0, c1 = before.counters, steps[-1].counters
    slots = c1["prefill_slots"] - c0["prefill_slots"]
    if slots <= 0:
        return None
    return 100.0 * (c1["prefill_tokens"] - c0["prefill_tokens"]) / slots
