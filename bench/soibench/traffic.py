"""Traffic from a mix file and ``--seed``.

One generator reads every mix. Lengths are log-uniform between ``lo`` and
``hi``; tenants are Zipf over a fixed set of shared prefixes; arrivals come
in bursts (exponential gaps between bursts, geometric burst sizes). This is
``repro.obs.loadgen.make_trace``'s shape, copied here so no later change to
the program moves the yardstick.

Every seed gets the same *set* of sizes, gaps and tenants, drawn at fixed
quantiles of those distributions, in another order; only the order and the
token ids change with the seed. So runs with different seeds do the same
work, and their spread is the system's, not the traffic's.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    """One request and what the serving loop observed of it."""
    rid: int
    client: int
    tokens: np.ndarray        # prompt ids
    gen_len: int              # output tokens wanted, first token included
    prefix_len: int = 0       # leading tokens shared with its tenant
    cached: int = 0           # prompt tokens the prefix cache served
    due: float = math.nan     # seconds after the window opened
    t_prefill: float = math.nan   # on the loop's clock
    times: list = dataclasses.field(default_factory=list)   # per token
    out: list = dataclasses.field(default_factory=list)     # token ids
    error: str | None = None

    @property
    def done(self) -> bool:
        return len(self.out) >= self.gen_len


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def log_uniform(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths at fixed quantiles of the log-uniform law on
    [lo, hi]."""
    return np.round(lo * (hi / lo) ** _quantiles(n)).astype(np.int64)


def geometric(mean: float, n: int) -> np.ndarray:
    """``n`` burst sizes at fixed quantiles of the geometric law (>= 1)."""
    if mean <= 1:
        return np.ones(n, np.int64)
    p = 1.0 / mean
    return np.maximum(1, np.ceil(np.log1p(-_quantiles(n))
                                 / np.log1p(-p))).astype(np.int64)


def exponential(mean: float, n: int) -> np.ndarray:
    return -mean * np.log1p(-_quantiles(n))


def zipf_tenants(n_tenants: int, a: float, n: int) -> np.ndarray:
    """``n`` tenant ranks at fixed quantiles of Zipf(``a``)."""
    w = 1.0 / np.arange(1, n_tenants + 1) ** a
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), n_tenants - 1)


class Traffic:
    """The requests of one run of a mix.

    ``pool`` requests' sizes are fixed by the mix; the seed permutes them
    and draws the token ids. A closed loop takes requests from the pool in
    turn (cycling, with fresh ids); an open loop takes the arrival schedule
    of :meth:`schedule`.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed) % 2 ** 64, 1])
        n = int(mix["pool"])
        p, o = mix["prompt"], mix["output"]
        # the pool's (prompt, output, tenant) triples are the mix's own, the
        # same for every seed; the seed orders them
        fixed = np.random.default_rng(0)
        prompt = log_uniform(p["lo"], p["hi"], n)
        output = fixed.permutation(log_uniform(o["lo"], o["hi"], n))
        pre = mix.get("prefix")
        tenant = (fixed.permutation(zipf_tenants(pre["tenants"],
                                                 pre["zipf_a"], n))
                  if pre else np.zeros(n, np.int64))
        order = self.rng.permutation(n)
        self.prompt_lens = prompt[order]
        self.output_lens = output[order]
        self.tenants = tenant[order]
        self.prefixes = (self.rng.integers(0, vocab, (pre["tenants"],
                                                      pre["len"]),
                                           dtype=np.int32)
                         if pre else None)
        self._next = 0

    def sizes(self) -> list:
        """(prompt length, output length) of every pool entry."""
        pre = 0 if self.prefixes is None else self.prefixes.shape[1]
        return [(pre + int(p), int(o))
                for p, o in zip(self.prompt_lens, self.output_lens)]

    def tenant_prompts(self) -> list:
        return [] if self.prefixes is None else list(self.prefixes)

    def take(self, client: int) -> Request:
        """The next request of the pool, for ``client``."""
        k = self._next
        self._next += 1
        i = k % len(self.prompt_lens)
        own = self.rng.integers(0, self.vocab, int(self.prompt_lens[i]),
                                dtype=np.int32)
        prefix_len = 0
        if self.prefixes is not None:
            prefix = self.prefixes[self.tenants[i]]
            own = np.concatenate([prefix, own])
            prefix_len = len(prefix)
        return Request(rid=k, client=client, tokens=own,
                       gen_len=int(self.output_lens[i]),
                       prefix_len=prefix_len)

    def schedule(self, seconds: float) -> list:
        """Open loop: the requests due in ``[0, seconds)``, at the mix's
        mean rate, in bursts. The gaps are scaled so the bursts span the
        window exactly, so every seed sends the same number."""
        rate, burst = float(self.mix["rate_hz"]), float(self.mix["burst_mean"])
        n_bursts = max(1, round(rate * seconds / burst))
        sizes = self.rng.permutation(geometric(burst, n_bursts))
        gaps = self.rng.permutation(exponential(1.0, n_bursts))
        # burst k opens at the k-th partial sum, the first at time 0
        starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        starts *= seconds / (starts[-1] + gaps[-1])
        out = []
        for start, size in zip(starts, sizes):
            for _ in range(int(size)):
                req = self.take(client=len(out))
                req.due = float(start)
                out.append(req)
        return out
