"""The one traffic generator: every mix is a data file it reads.

A mix file gives the closed loop's ``clients`` (each sends its next
request when its last one finishes), the prompt and output length
distributions, where they come from (``source``), and how many served
requests the correctness check compares (``check_requests``). A length
distribution is ``lognormal``, by its ``median`` and ``sigma`` (of the
natural logarithm): real prompt and output lengths have heavy tails.

Every seed serves the same work in another order. Lengths are drawn in
blocks of ``BLOCK`` requests: each block holds the distribution's
``BLOCK`` stratified quantiles (the midpoints of equal probability bins),
permuted by the seed, so two seeds differ in order and token ids but not
in how much there is to do. Token ids are uniform over the vocabulary.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, Iterator

import numpy as np

BLOCK = 16


@dataclasses.dataclass
class Planned:
    index: int
    prompt: np.ndarray               # [P] int32
    max_new: int


def _quantile(dist: Dict, u: np.ndarray) -> np.ndarray:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
    x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.maximum(np.rint(x), 1).astype(np.int64)


def longest(dist: Dict) -> int:
    """The longest length a block holds: its highest quantile."""
    return int(_quantile(dist, np.array([(BLOCK - 0.5) / BLOCK]))[0])


def _stratified(rng: np.random.Generator) -> np.ndarray:
    """One block of stratified probabilities, in the seed's order."""
    return (rng.permutation(BLOCK) + 0.5) / BLOCK


def requests(mix: Dict, vocab: int, rng: np.random.Generator
             ) -> Iterator[Planned]:
    """The mix's request stream, without end."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    i = 0
    while True:
        plen = _quantile(mix["prompt"], _stratified(rng))
        olen = _quantile(mix["output"], _stratified(rng))
        for j in range(BLOCK):
            prompt = rng.integers(0, vocab, int(plen[j]), dtype=np.int32)
            yield Planned(i, prompt, int(olen[j]))
            i += 1
