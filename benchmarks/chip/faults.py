"""Faults planted in the timed path, to show that what should see them
does: ``correct`` for a wrong KV read or an altered token, the rates
for a slower decode step.  ``calibrate.py --fault <name>`` plants one on
the chip, the tests on the CPU; the benchmark's own runs plant none.
Each takes the node's engine and scheduler right after they are
built."""
import time

import jax
import jax.numpy as jnp

SLOW_DECODE_S = 0.02


def wrong_page_table(eng, sched):
    """Every decode row reads its neighbour's pages: a wrong KV read (the
    wrong-page-table control of ``chip_smoke.py``)."""
    dec = eng._decode_batched

    def rolled(params, arena, pt, *rest):
        return dec(params, arena, jnp.roll(pt, 1, axis=0), *rest)
    eng._decode_batched = rolled


def altered_token(eng, sched):
    """The third token of every request altered where the scheduler
    produces it."""
    step, vocab = sched.step, eng.cfg.vocab

    def altered():
        step()
        for s in sched.slots:
            if s is not None and len(s.out) == 3:
                s.out[-1] = (s.out[-1] + 1) % vocab
    sched.step = altered


def slow_decode(eng, sched):
    """Every decode dispatch takes SLOW_DECODE_S longer: the step waits
    for the decode's result, then sleeps.  A delay on the host alone
    would hide behind the device, since the scheduler reads each
    decode's tokens only in the next step."""
    dec = eng._decode_batched

    def slower(*args):
        out = jax.block_until_ready(dec(*args))
        time.sleep(SLOW_DECODE_S)
        return out
    eng._decode_batched = slower


FAULTS = {f.__name__: f for f in (wrong_page_table, altered_token,
                                  slow_decode)}
