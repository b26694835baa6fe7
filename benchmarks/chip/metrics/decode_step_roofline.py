"""Kernels of the decode step (paged attention and the matmuls): the
least time a decode step could take on this chip over the time it took,
in percent.  The least time is the larger of its least bytes (every
weight once, the batch's embedding rows, the K/V of the live tokens of
the active rows) over HBM bandwidth and its operations over peak
FLOP/s, averaged over the window's dispatches; the time taken is the
decode program's device time per dispatch, from the trace."""
import arith
from devtrace import program_time


def read(run):
    if run.red is None or not run.win.decode_ctx:
        return None
    secs, n = program_time(run.red, "_decode_paged_batched")
    if not n:
        return None
    pk, spec = run.peaks, run.spec
    least = [max(arith.decode_least_bytes(spec, c) / pk["hbm_bytes_per_s"],
                 arith.decode_flops(spec, c) / pk["bf16_flops"])
             for c in run.win.decode_ctx]
    return 100.0 * (sum(least) / len(least)) / (secs / n)
