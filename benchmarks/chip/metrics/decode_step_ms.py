"""Model step (``decode_paged``): device time per dispatch of the batched
decode program, from the trace."""
from devtrace import program_time


def read(run):
    if run.red is None:
        return None
    secs, n = program_time(run.red, "_decode_paged_batched")
    return secs / n * 1e3 if n else None
