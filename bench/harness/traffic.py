"""One general generator for every traffic mix.

A mix file (``traffic/<mix>.json``) gives the length distributions; the
cell file gives the offered rate.  Arrivals are open loop: requests are due
on a schedule fixed before the run, whatever the server does.

Every seed gets the same set of sizes and gaps in another order, so the
seed changes which request comes when and every prompt's tokens, but not
how much work a run offers: the ``n`` prompt lengths are the distribution's
quantiles at ``(i + 1/2) / n``, and likewise the output lengths and the
exponential gaps between arrivals (a Poisson process at the cell's rate).

The order is stratified: each of the three sorted sets is dealt into
blocks of about ``block`` consecutive requests (the mix's ``block``,
default 8) so that every block holds one value from each of ``block``
strata; the seed shuffles the values inside each block and the order of
the blocks, each set on its own.  Any ``block`` requests in a row then
offer about the work of any other ``block``, and the window's edges cut
the same amount of work whatever the seed.  ``block: 1`` is a plain
shuffle.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float            # seconds after the phase opens
    prompt: np.ndarray    # token ids
    output_len: int

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(int)


def gaps(rate: float, n: int) -> np.ndarray:
    """Exponential quantiles: the gaps of a Poisson process at ``rate``."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def stratified(values: np.ndarray, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``values`` in blocks of ``block`` (some one shorter), each holding
    one entry of every stratum of the sorted values: block ``j`` takes the
    sorted entries ``j, j + nb, j + 2 nb, ...`` of ``nb`` blocks."""
    v = np.sort(values)
    nb = -(-len(v) // block)
    out = [x for j in rng.permutation(nb) for x in rng.permutation(v[j::nb])]
    return np.asarray(out, dtype=values.dtype)


def requests(mix: Dict, rate: float, seconds: float, vocab: int,
             seed: int, stream: int, first_rid: int = 0) -> List[Req]:
    """``round(rate * seconds)`` requests due over ``[0, seconds)``.

    ``stream`` separates independent draws of one run (warm-up, window)."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), stream])
    block = int(mix.get("block", 8))
    prompts = stratified(_quantiles(mix["prompt"], n), block, rng)
    outputs = stratified(_quantiles(mix["output"], n), block, rng)
    g = stratified(gaps(rate, n), block, rng)
    # the quantile gaps sum to about n / rate; scale them onto the phase
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    due *= seconds / max(float(np.sum(g)), 1e-9)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, int(prompts[i]), dtype=np.int32)
        out.append(Req(first_rid + i, float(due[i]), toks, int(outputs[i])))
    return out

