"""The node's step records over the window, for the readers of the
program's own spans and counters (``repro.serving.trace``).

The window's steps are the last ``len(run.win.steps)`` records of the
newest live recorder: the harness calls ``Scheduler.step`` nowhere else
between set-up and the readers.  A program without a recorder, or a ring
that no longer holds the record before the window, gives ``None``.
"""


def window(run):
    """(the record before the window, [the window's records]), or None."""
    try:
        from repro.serving import trace
    except ImportError:
        return None
    rec = trace.latest()
    n = len(run.win.steps)
    if rec is None or not n or len(rec.steps) < n + 1:
        return None
    recs = list(rec.steps)[-(n + 1):]
    return recs[0], recs[1:]
