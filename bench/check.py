"""Whether the timed path served correct tokens.

After the window, a sample of the requests it finished (drawn from the
seed, with the longest in it) is scored by the configuration's plain
float32 reference over each prompt and its served tokens.  A served token
is judged by its gap: how far its reference logit lies below the
reference's best logit at that position.  Greedy decoding in bfloat16
picks the reference's best token or one within rounding of it; a wrong
token, a wrong position or a broken cache lands far below.  The numbers
compared are those of ``summary`` that the cell's file
(``cells/<cell>.json``) gives a limit: the widest gap over every sampled
token, or the mean gap where the widest does not separate the program
from the control (see PERF.md)."""

from __future__ import annotations

import numpy as np


def sample(clients: list, k: int, seed: int) -> list:
    """``k`` finished requests: the one with most served tokens, and the
    rest drawn from the seed."""
    done = [c for c in clients if c.done and c.request.generated]
    if not done:
        return []
    done.sort(key=lambda c: (len(c.request.generated),
                             len(c.request.prompt)), reverse=True)
    rest = done[1:]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


def sequence(prompt, generated, length: int):
    """Reference input (prompt and every served token but the last, padded
    to ``length``, so that one program serves every request), the served
    tokens as targets at their positions, and the slice of positions that
    predict them."""
    import jax.numpy as jnp

    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated, np.int32)])[:-1]
    n = len(seq)
    toks = np.zeros(length, np.int32)
    toks[:n] = seq
    targets = np.zeros(length, np.int32)
    P = len(prompt)
    targets[P - 1:n] = np.asarray(generated, np.int32)
    return jnp.asarray(toks), jnp.asarray(targets), slice(P - 1, n)


def summary(gaps: list) -> dict:
    """The numbers a cell may compare (``cells/<cell>.json`` ``limits``
    names which): the widest and the mean gap over every sampled token."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(g.max()) if g.size else None,
            "mean_logit_gap": float(g.mean()) if g.size else None,
            "tokens": int(g.size)}


def served_gaps(ref, w, m: dict, requests: list, length: int) -> dict:
    """Score each request's served tokens (sequences padded to
    ``length``)."""
    m_items = tuple(sorted(m.items()))
    gaps, off = [], 0
    for r in requests:
        toks, targets, sl = sequence(r.prompt, r.generated, length)
        gap, arg, _ = ref.gaps(w, m_items, toks, targets)
        gaps.append(np.asarray(gap)[sl])
        off += int((np.asarray(arg)[sl] != np.asarray(r.generated)).sum())
    return dict(summary(gaps), not_reference_best=off,
                requests=len(requests))


def control_gaps(ref, w, m: dict, requests: list, length: int,
                 precision: str = "fp8") -> dict:
    """The control: at each position of the same sequences, the gap of the
    token that the reference computed at ``precision`` puts first."""
    m_items = tuple(sorted(m.items()))
    gaps = []
    for r in requests:
        toks, targets, sl = sequence(r.prompt, r.generated, length)
        ctl = ref.choices(w, m_items, toks, precision)
        _, _, cgap = ref.gaps(w, m_items, toks, targets, ctl)
        gaps.append(np.asarray(cgap)[sl])
    return dict(summary(gaps), requests=len(requests))
