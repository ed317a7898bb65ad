"""The one generator every mix goes through.

A mix is a data file (``bench/traffic/<name>.json``).  Every seed gets
the same multiset of sizes and gaps -- stratified quantiles of the mix's
distributions -- in an order of its own, so a seed changes which request
comes when and what its tokens are, not how much work a run holds.

Length distributions, as a mix file states them:

* ``{"choice": [a, b, c], "weights": [wa, wb, wc]}``
* ``{"lognormal": {"median": m, "sigma": s}, "min": lo, "max": hi}``
* ``{"uniform": [lo, hi]}`` (integers, both ends included)
"""
from __future__ import annotations

import math

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one purpose of one seed (any size of seed)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _norm_ppf(u: np.ndarray) -> np.ndarray:
    # Acklam's rational approximation of the standard normal quantile
    # (relative error < 1.2e-9), so numpy alone suffices.
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    u = np.asarray(u, np.float64)
    out = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(np.where(lo, u, 1 - u)))
    tail = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
            + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    out[lo] = tail[lo]
    out[hi] = -tail[hi]
    r = (u - 0.5)[mid]
    s = r * r
    out[mid] = ((((((a[0] * s + a[1]) * s + a[2]) * s + a[3]) * s + a[4]) * s
                 + a[5]) * r / (((((b[0] * s + b[1]) * s + b[2]) * s + b[3])
                                * s + b[4]) * s + 1))
    return out


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths at probabilities ``u`` of a mix's distribution."""
    u = np.asarray(u, np.float64)
    if "choice" in dist:
        vals = np.asarray(dist["choice"])
        w = np.asarray(dist.get("weights", [1] * len(vals)), np.float64)
        cum = np.cumsum(w / w.sum())
        return vals[np.minimum(np.searchsorted(cum, u, side="right"),
                               len(vals) - 1)].astype(np.int64)
    if "lognormal" in dist:
        ln = dist["lognormal"]
        x = ln["median"] * np.exp(ln["sigma"] * _norm_ppf(u))
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {dist}")


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int, warm: int = 0) -> dict:
    """Requests of an open-loop run at ``rate`` per second.

    Returns ``{"due": (n,) seconds after the window opens, "prompt":
    [token arrays], "out": (n,) output lengths}`` for the arrivals, and
    the same keys under ``"warm"`` for ``warm`` requests already in
    flight when the window opens: their outputs follow the remaining
    life of a request caught in progress (length-biased, uniformly far
    through), so the window opens on a batch that looks like its steady
    state.
    """
    n = max(1, int(math.ceil(rate * seconds)))
    r = rng(seed, 1)
    gaps = -np.log1p(-strata(n)) / rate          # exponential quantiles
    due = np.cumsum(r.permutation(gaps))
    plen = r.permutation(quantiles(mix["prompt_len"], strata(n)))
    olen = r.permutation(quantiles(mix["output_len"], strata(n)))
    out = {"due": due, "prompt_len": plen, "out": olen}
    if warm:
        # Remaining output of a request in progress: draw from the
        # length-biased law (a fixed grid, not the seed), then take the
        # stratified quantiles of U * L.
        grid = quantiles(mix["output_len"], strata(4096)).astype(np.float64)
        g = rng(0, 7)
        life = g.choice(grid, size=16384, p=grid / grid.sum())
        rest = np.maximum(1, np.rint(life * g.random(16384)))
        wl = np.quantile(rest, strata(warm)).astype(np.int64)
        out["warm"] = {
            "prompt_len": r.permutation(quantiles(mix["prompt_len"],
                                                  strata(warm))),
            "out": r.permutation(wl)}
    tok = rng(seed, 2)
    out["prompt"] = [tok.integers(0, vocab, int(s), dtype=np.int32)
                     for s in out["prompt_len"]]
    if warm:
        out["warm"]["prompt"] = [tok.integers(0, vocab, int(s),
                                              dtype=np.int32)
                                 for s in out["warm"]["prompt_len"]]
    return out


def train_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> dict:
    """Token rows of one training step: uniform ids, every row of every
    step different."""
    r = rng(seed, 3, step)
    seq = r.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
    return {"tokens": seq[:, :-1], "targets": seq[:, 1:]}
