"""How ``correct`` is decided: served tokens against the float32
reference.

After the window, a sample of the requests it served (finished, or still
decoding at the close with the tokens emitted so far), drawn from the
seed, with the one that served the most tokens and the one that reused
the most cached prompt tokens in it.  The reference runs once over each
prompt with its served tokens; at each served token the gap by which the
token's reference logit lies below the reference's best is read, and the
widest gap is compared with the cell's limit (``limits/<workload>.json``).
Greedy decoding makes every served token the program's own best, so a
wrong KV read, a wrong page or a token altered on its way out shows as a
wide gap.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import reference

HERE = Path(__file__).resolve().parent
MIN_TOKENS = 400        # served tokens a sample should hold
MAX_REQUESTS = 8
POS_BUCKET = 256        # positions padded to a multiple: few head shapes


def load_limits(workload: str) -> dict:
    return json.loads((HERE / "limits" / f"{workload}.json").read_text())


def sample(served: list, seed: int) -> list:
    """``(rid, tokens, cached)`` of the requests to check, from those the
    window served (finished, or decoding at the close with what they have
    emitted): the one with the most served tokens, the one with the most
    cached prompt tokens, then others in an order drawn from the seed
    until the sample holds MIN_TOKENS served tokens or MAX_REQUESTS
    requests."""
    done = [r for r in served if r[1]]
    if not done:
        return []
    pick = [max(done, key=lambda r: len(r[1])),
            max(done, key=lambda r: r[2])]
    if pick[1][0] == pick[0][0]:
        pick.pop()
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(done)):
        if (sum(len(r[1]) for r in pick) >= MIN_TOKENS
                or len(pick) >= MAX_REQUESTS):
            break
        if done[i][0] not in {r[0] for r in pick}:
            pick.append(done[i])
    return pick


def teacher_forced(prompts: dict, picked: list):
    """Token lists and the positions whose next token is served."""
    seqs, want, served = [], [], []
    for rid, out, _ in picked:
        p = list(prompts[rid])
        seqs.append(p + list(out[:-1]))
        want.append(np.arange(len(p) - 1, len(p) - 1 + len(out)))
        served.append(np.asarray(out))
    return seqs, want, served


def ref_length(max_len: int) -> int:
    """The reference's padded length: ``max_len`` up to its query block."""
    q = reference.QUERY_BLOCK
    return -(-max_len // q) * q


def _padded(pos):
    n = -(-len(pos) // POS_BUCKET) * POS_BUCKET
    return np.concatenate([pos, np.full(n - len(pos), pos[-1])])


def reference_logits(spec, seed, seqs, want, length, quant=None):
    got = reference.logits_at(spec, seed, seqs, [_padded(w) for w in want],
                              length, quant=quant)
    return [np.asarray(g, np.float64)[:len(w)] for g, w in zip(got, want)]


def widest_gap(ref: list, chosen: list) -> float:
    """Widest ``ref best - ref[chosen]`` over every checked position."""
    gaps = [(r.max(-1) - r[np.arange(len(c)), c]).max()
            for r, c in zip(ref, chosen)]
    return float(max(gaps))


def judge(spec, seed, prompts, served, length, limits,
          control=False) -> dict:
    """The numbers compared, each beside its limit, and the verdict.
    With ``control``, the reference in float8 (the control) is also put in
    the program's place: the tokens it puts first at the same prompts and
    positions are judged by the same rule, under ``control``."""
    picked = sample(served, seed)
    checks = {"requests_checked": {"value": len(picked), "rule": ">=",
                                   "limit": 1}}
    out = {"correct": False, "checks": checks}
    if not picked:
        return out
    seqs, want, chosen = teacher_forced(prompts, picked)
    ref = reference_logits(spec, seed, seqs, want, ref_length(length))
    finite = all(np.isfinite(r).all() for r in ref)
    lim = limits["logit_gap_max"]["limit"]

    def gap_check(tokens):
        gap = widest_gap(ref, tokens) if finite else float("inf")
        return {"value": gap, "rule": "<=", "limit": lim}, gap <= lim

    checks["tokens_checked"] = {"value": int(sum(map(len, chosen))),
                                "rule": ">=", "limit": 1}
    checks["logit_gap_max"], ok = gap_check(chosen)
    out["correct"] = bool(ok)
    if control:
        ctl = reference_logits(spec, seed, seqs, want, ref_length(length),
                               quant="fp8")
        c, c_ok = gap_check([c.argmax(-1) for c in ctl])
        out["control"] = {"correct": bool(c_ok),
                          "checks": {"logit_gap_max": c}}
    return out
