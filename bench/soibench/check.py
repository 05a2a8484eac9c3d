"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the one with the most served tokens, is
replayed through the plain reference (:mod:`soibench.reference`, with the
layers of the configuration's family module): each prompt with its served
tokens, in one forward pass. For every served token
the reference gives the gap by which that token's logit lies below its own
best logit at that position. Greedy serving that computes what the
configuration states keeps the widest gap small; the number compared is
that widest gap, against the cell's limit (``bench/limits/<cell>.json``,
set from the readings listed in ``PERF.md``).
"""

from __future__ import annotations

import numpy as np

from soibench import reference


def sample(finished: list, k: int, seed: int) -> list:
    """Up to ``k`` finished requests: the one with the most served tokens,
    then others drawn from the seed."""
    done = [r for r in finished if r.error is None and r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % 2 ** 64, 2])
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _sequence(req, length: int):
    """The prompt and the served tokens fed back (right-padded to
    ``length``), and the positions whose next token was served."""
    tl, n = len(req.tokens), len(req.out)
    seq = np.zeros(length, np.int32)
    seq[:tl] = req.tokens
    seq[tl:tl + n - 1] = req.out[:-1]
    return seq, slice(tl - 1, tl - 1 + n)


def served_gaps(fam, weights, sizes: dict, req, length: int) -> np.ndarray:
    """Reference gap of each token ``req`` was served (first token first)."""
    import jax.numpy as jnp
    seq, served = _sequence(req, length)
    targets = np.zeros(length, np.int32)
    targets[served] = req.out
    g = reference.gaps(fam, weights, reference.frozen(sizes),
                       jnp.asarray(seq), jnp.asarray(targets))
    return np.asarray(g)[served]


def compare(fam, weights, sizes: dict, reqs: list, max_len: int) -> dict:
    """The widest gap over every served token of ``reqs``, and where it
    lies: the prefill's first token, decode steps at phase 0 (the middle
    ran) and off-phase steps (it was extrapolated)."""
    length = reference.padded_len(max_len, sizes["stride"])
    parts = {"first": [0.0], "phase0": [0.0], "offphase": [0.0]}
    tokens = 0
    for req in reqs:
        g = served_gaps(fam, weights, sizes, req, length)
        tokens += len(g)
        parts["first"].append(float(g[0]))
        # token k >= 1 came from the generate step whose input sat at
        # position tl + k - 1
        pos = len(req.tokens) + np.arange(1, len(g)) - 1
        on = pos % sizes["stride"] == 0
        parts["phase0"] += g[1:][on].tolist()
        parts["offphase"] += g[1:][~on].tolist()
    widest = {k: float(max(v)) for k, v in parts.items()}
    return {"max_gap": max(widest.values()), "tokens": tokens,
            "requests": len(reqs), **{f"max_gap_{k}": v
                                      for k, v in widest.items()}}


def control(fam, weights, sizes: dict, reqs: list, max_len: int) -> float:
    """The control's reading: over the same prompts and served tokens, the
    widest reference gap of the token that the float8 reference puts
    first at each served position."""
    import jax.numpy as jnp
    length = reference.padded_len(max_len, sizes["stride"])
    widest = 0.0
    for req in reqs:
        seq, served = _sequence(req, length)
        g = reference.control_gaps(fam, weights, reference.frozen(sizes),
                                   jnp.asarray(seq))
        widest = max(widest, float(np.max(np.asarray(g)[served])))
    return widest
