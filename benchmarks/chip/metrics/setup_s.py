"""Process start to window start: weights, engine, compilation (or the
compile cache), and the set-up traffic that brings the node to its
steady state.  Host clock."""


def read(run):
    return run.setup_s
