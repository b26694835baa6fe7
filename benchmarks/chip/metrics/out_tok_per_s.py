"""Every output token emitted in the window over the window's seconds, up
to the end of its last step: all the work over all the time.  Host
clock."""


def read(run):
    win = run.win
    return win.token_count() / win.elapsed if win.elapsed > 0 else None
