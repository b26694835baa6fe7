"""Whole step: model operations of every token the window processed
(prefill of the uncached prompt positions of each admission, and one
token per active row of each decode step) over the traced window's
seconds times the chip's bf16 peak, in percent."""
import arith


def read(run):
    if run.red is None or run.red.window_s <= 0:
        return None
    spec = run.spec
    flops = sum(arith.decode_flops(spec, c) for c in run.win.decode_ctx)
    flops += sum(arith.prefill_flops(spec, c, p) for c, p in run.win.admitted)
    if not flops:
        return None
    return 100.0 * flops / (run.red.window_s * run.peaks["bf16_flops"])
