"""Median over requests of the time per output token inside the window:
(last token - first token) / (tokens - 1), for requests whose tokens in
the window span at least a quarter second, so that every reading spans
many steps of the host clock.  Host clock."""
import numpy as np

MIN_SPAN_S = 0.25


def read(run):
    per = [(t[-1] - t[0]) / (len(t) - 1) for t in run.win.tokens.values()
           if len(t) > 1 and t[-1] - t[0] >= MIN_SPAN_S]
    return float(np.median(per)) * 1e3 if per else None
