"""Offline backlog: the paper's Coding statistics (Zipf over shared
prefixes, a long suffix, a fixed output budget), in a queue that never
runs dry.  The node starts the window full: one request per slot already
in flight, with a residual output budget spread evenly over
(0, max_new] and its earlier output in its context."""
import numpy as np

from traffic import Request, Traffic, tokens, zipf_choice


def generate(mix: dict, vocab: int, max_active: int, seed: int) -> Traffic:
    srng = np.random.default_rng(mix["sizes_seed"])
    n_pre = mix["prefixes"]
    pre_len = np.maximum(8, srng.normal(mix["prefix_len_mean"],
                                        mix["prefix_len_sd"],
                                        n_pre).astype(int))
    n = mix["requests"] + max_active
    pre_id = zipf_choice(srng, n_pre, mix["prefix_zipf"], n)
    suf = np.maximum(4, srng.normal(mix["suffix_len_mean"],
                                    mix["suffix_len_sd"], n).astype(int))
    max_new = mix["max_new"]
    # the suffix is clipped so that prompt + output fits the context
    suf = np.minimum(suf, mix["max_context"] - max_new - pre_len[pre_id])
    resid = srng.permutation([int(round(max_new * (i + 0.5) / max_active))
                              for i in range(max_active)]).tolist()
    rng = np.random.default_rng(seed)
    prefixes = [tokens(rng, vocab, ln) for ln in pre_len]
    out = Traffic()
    for rid in range(n):
        prompt = prefixes[pre_id[rid]] + tokens(rng, vocab, suf[rid])
        if rid < max_active:
            earlier = tokens(rng, vocab, max_new - resid[rid])
            out.in_flight.append(Request(rid, prompt + earlier,
                                         max_new=resid[rid]))
        else:
            out.backlog.append(Request(rid, prompt, max_new=max_new))
    return out
