"""Decide ``correct``: served tokens against the plain reference.

Once the window has closed and the engine's state is freed, a sample of the
requests the run finished, drawn from the seed with the longest among them,
is run through the reference once: each prompt with its served tokens.  At
every served position the number compared is the gap by which the served
token's reference logit lies below the reference's best logit there.  The
tokens are greedy, so a sound program serves the reference's argmax up to
rounding, and the gap is 0 or small; a wrong page, position, cache row or
expert moves it by about the spread of the logits.
"""
from __future__ import annotations

import numpy as np


def sample(run, seed: int, n: int) -> list[int]:
    """Plan indices of ``n`` finished requests: the one with the most served
    tokens, then one from each other engine slot in an order drawn from
    the seed (a fault in one slot's pages, rows or position shows only in
    the requests that slot served), then others drawn from the seed."""
    done = sorted((i for i, s in run.served.items() if s.ok),
                  key=lambda i: (-len(run.requests[i].out), i))
    if not done:
        return []
    rng = np.random.default_rng(seed)
    picked = [done[0]]
    by_slot: dict = {}
    for i in done[1:]:
        by_slot.setdefault(run.served[i].slot, []).append(i)
    slots = sorted((s for s in by_slot if s != run.served[done[0]].slot),
                   key=str)
    for j in rng.permutation(len(slots)):
        if len(picked) >= n:
            break
        group = by_slot[slots[j]]
        picked.append(group[rng.integers(len(group))])
    rest = [i for i in done[1:] if i not in picked]
    more = rng.choice(len(rest), size=min(n - len(picked), len(rest)),
                      replace=False)
    return [picked[0]] + sorted(picked[1:] + [rest[j] for j in more])


def sequences(run, idxs: list[int]):
    """(tokens fed, positions read, tokens served) of each sampled request:
    served token ``j`` came from the logits at position ``len(prompt)-1+j``,
    and every served token but the last is fed back."""
    seqs, at, served = [], [], []
    for i in idxs:
        req = run.requests[i]
        out = np.asarray(req.out, np.int32)
        n = len(req.prompt)
        seqs.append(np.concatenate([req.prompt, out[:-1]]))
        at.append(np.arange(n - 1, n - 1 + len(out)))
        served.append(out)
    return seqs, at, served


def gaps(ref: list[np.ndarray], chosen: list[np.ndarray]) -> np.ndarray:
    """Reference best logit minus the reference logit of the chosen token,
    at every position."""
    return np.concatenate([r.max(-1) - r[np.arange(len(c)), c]
                           for r, c in zip(ref, chosen)])


def summary(g: np.ndarray) -> dict:
    """The numbers compared: the widest gap and the mean gap over every
    compared position, and how many positions there were."""
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "tokens_compared": int(g.size)}


def served_gap(family, weights, dims, run, idxs, *, pad_to: int) -> dict:
    """``summary`` of the served tokens' gaps against the reference of
    ``family`` (empty when nothing finished)."""
    if not idxs:
        return {"tokens_compared": 0}
    seqs, at, served = sequences(run, idxs)
    return summary(gaps(_by_request(family, weights, dims, seqs, at, pad_to),
                        served))


def _by_request(family, weights, dims, seqs, at, pad_to, mode=None):
    """The reference's logits, one request at a time, so that a batch of
    long contexts need not fit at once."""
    return [family.logits(weights, dims, [s], [a], pad_to=pad_to,
                          mode=mode)[0] for s, a in zip(seqs, at)]


def control_gap(family, weights, dims, run, idxs, *, pad_to: int,
                mode: str) -> tuple[dict, dict]:
    """(``summary`` of the served tokens' gaps, of the gaps of the tokens
    the reference computed in ``mode`` puts first), at the same positions."""
    seqs, at, served = sequences(run, idxs)
    ref = _by_request(family, weights, dims, seqs, at, pad_to)
    low = _by_request(family, weights, dims, seqs, at, pad_to, mode)
    return (summary(gaps(ref, served)),
            summary(gaps(ref, [x.argmax(-1) for x in low])))
