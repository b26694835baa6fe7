"""Admission (serving/scheduler.py): active rows per decode dispatch in
the window, mean.  Counted from the scheduler's slots after each step."""


def read(run):
    rows = [len(c) for c in run.win.decode_ctx]
    return sum(rows) / len(rows) if rows else None
