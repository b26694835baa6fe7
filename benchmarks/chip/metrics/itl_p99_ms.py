"""99th percentile of every gap between consecutive output tokens of
every request inside the window: where prefill stalls decode.  Host
clock."""
import numpy as np


def read(run):
    gaps = np.concatenate([np.diff(t) for t in run.win.tokens.values()
                           if len(t) > 1] or [np.zeros(0)])
    return float(np.percentile(gaps, 99)) * 1e3 if gaps.size else None
