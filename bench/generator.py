"""The one traffic generator: reads a traffic file's parameters and makes
a cell's requests from ``--seed``.

Every seed gets the same number of requests, all of one length, and the
same set of gaps between arrivals, in an order the seed draws, so two
seeds offer the same work and differ only in which tokens arrive when.

Traffic file keys:

* ``tokens``: ``template``, clause skeletons with variable slots, the
  input similarity memoization relies on (copied from the program's
  ``data/synthetic.py`` so a later change there cannot move the
  yardstick): ``n_templates`` skeletons, a ``slot_fraction`` of positions
  redrawn per request. The store's calibration passages come from the
  same skeletons.
* ``length``: the tokens of every request.
* ``arrivals``: ``poisson`` at ``rate_per_s`` (exponential gaps), or
  ``backlog`` (``count`` requests, all due at 0).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class TemplateCorpus:
    """Template-grammar token sequences: each sample instantiates one of
    ``n_templates`` fixed skeletons and redraws a ``slot_fraction`` of its
    positions, so inputs of one template share most of their tokens."""
    vocab: int
    seq_len: int
    n_templates: int = 8
    slot_fraction: float = 0.25
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        skel_hi = max(2, int(self.vocab * 0.6))
        self._skeletons = rng.integers(
            1, skel_hi, (self.n_templates, self.seq_len))
        n_slots = max(1, int(self.seq_len * self.slot_fraction))
        self._slot_pos = np.stack([
            rng.choice(self.seq_len, n_slots, replace=False)
            for _ in range(self.n_templates)])
        self._slot_lo = skel_hi

    def sample(self, n: int, rng) -> np.ndarray:
        """(n, seq_len) int32 token ids."""
        t_ids = rng.integers(0, self.n_templates, n)
        toks = self._skeletons[t_ids].copy()
        fills = rng.integers(self._slot_lo, self.vocab,
                             (n, self._slot_pos.shape[1]))
        toks[np.arange(n)[:, None], self._slot_pos[t_ids]] = fills
        return toks.astype(np.int32)


@dataclass
class Request:
    arrival: float          # seconds after the window opens
    tokens: np.ndarray      # (length,) int32


def corpus(traffic: dict, vocab: int, seed: int) -> TemplateCorpus:
    """The template family of this seed: calibration and traffic draw
    from the same skeletons."""
    if traffic["tokens"] != "template":
        raise ValueError(f"unknown tokens {traffic['tokens']!r}")
    return TemplateCorpus(
        vocab=vocab, seq_len=int(traffic["length"]),
        n_templates=int(traffic["n_templates"]),
        slot_fraction=float(traffic["slot_fraction"]), seed=seed)


def arrivals(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Sorted arrival times in [0, seconds): the same gaps for every seed
    (exponential quantiles at the cell's rate), shuffled by the seed."""
    kind = traffic["arrivals"]
    if kind == "backlog":
        return np.zeros(int(traffic["count"]))
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    arr = np.cumsum(rng.permutation(gaps))
    # the gaps sum to ~n/rate == seconds; scale so the last one lands
    # inside the window
    return arr * (seconds * (1 - 0.5 / n) / max(arr[-1], 1e-12))


def calibration(templates: TemplateCorpus, n: int, seed: int
                ) -> np.ndarray:
    """The ``n`` sequences the store is built from: (n, seq_len)."""
    return templates.sample(n, np.random.default_rng([seed, 0]))


def requests(traffic: dict, seconds: float, seed: int,
             templates: TemplateCorpus, stream: int = 0) -> List[Request]:
    """The requests of one window. ``stream`` separates the window's
    draws from warm-up and calibration draws of the same seed."""
    rng = np.random.default_rng([seed, 1 + stream])
    arr = arrivals(traffic, seconds, rng)
    toks = templates.sample(arr.size, rng)
    return [Request(float(a), t) for a, t in zip(arr, toks)]
