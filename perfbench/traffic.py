"""The one traffic generator: a mix file's parameters and a seed in, a
deterministic stream of requests out.

A mix is a closed loop (``clients`` that each send their next request when
the last one finishes) or an open loop (arrivals at ``rate_per_s``, Poisson).
Every size is drawn by stratified sampling: each seed gets the same set of
values (the ``POOL`` evenly spaced quantiles of the distribution) in its own
order, so seeds change the order of the work and not its amount. Token ids
are uniform over the vocabulary. Requests may share one of ``documents``
seeded documents as a prefix (the prefix cache's work) or share nothing.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

POOL = 256


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.default_rng([int(seed) % (1 << 64), tag])


def quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "exponential":
        return -math.log(1.0 - u) / float(dist["rate"])
    lo, hi = float(dist["low"]), float(dist["high"])
    if kind == "uniform":
        return lo + u * (hi - lo)
    if kind == "loguniform":
        return math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    raise ValueError(f"unknown distribution {kind!r}")


class Stratified:
    """Values of ``dist`` at the POOL evenly spaced quantiles, handed out in
    a seeded order, one permutation of the pool after another."""

    def __init__(self, dist: dict, g: np.random.Generator, integer=True):
        self.values = [quantile(dist, (i + 0.5) / POOL) for i in range(POOL)]
        if integer:
            self.values = [int(round(v)) for v in self.values]
        self.g, self.order, self.i = g, [], 0

    def next(self):
        if self.i >= len(self.order):
            self.order, self.i = self.g.permutation(POOL).tolist(), 0
        v = self.values[self.order[self.i]]
        self.i += 1
        return v


@dataclasses.dataclass
class Spec:
    """One request as the traffic defines it (before the engine sees it)."""
    index: int
    doc: int | None            # shared document, or None
    prompt: np.ndarray         # full prompt: [document +] unique tokens
    max_new: int
    due: float = 0.0           # open loop: seconds after the window opens


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab = mix, vocab
        docs = mix.get("documents")
        g = rng(seed, "documents")
        self.documents = [g.integers(0, vocab, docs["tokens"], np.int32)
                          for _ in range(docs["count"])] if docs else []
        self._tok = rng(seed, "tokens")
        self._unique = Stratified(mix["unique_prompt_tokens"],
                                  rng(seed, "unique"))
        self._out = Stratified(mix["output_tokens"], rng(seed, "output"))
        self._doc = Stratified({"dist": "uniform", "low": 0, "high": 1},
                               rng(seed, "doc"), integer=False)
        self._gap = (Stratified({"dist": "exponential",
                                 "rate": mix["rate_per_s"]},
                                rng(seed, "arrivals"), integer=False)
                     if mix["loop"] == "open" else None)
        self._n, self._t = 0, 0.0

    @property
    def closed(self) -> bool:
        return self.mix["loop"] == "closed"

    def next(self) -> Spec:
        doc = (min(int(self._doc.next() * len(self.documents)),
                   len(self.documents) - 1) if self.documents else None)
        n_unique = self._unique.next()
        unique = self._tok.integers(0, self.vocab, n_unique, np.int32)
        prompt = (np.concatenate([self.documents[doc], unique])
                  if doc is not None else unique)
        if self._gap is not None:
            self._t += self._gap.next()
        spec = Spec(self._n, doc, prompt, int(self._out.next()), self._t)
        self._n += 1
        return spec

    def longest_output(self) -> int:
        return max(self._out.values)

    def mean_output(self) -> float:
        return float(np.mean(self._out.values))

    def longest_unique(self) -> int:
        return max(self._unique.values)
