"""One general traffic generator: a traffic mix is a JSON file of
parameters (``chipbench/traffic/<mix>.json``) and this module turns it into
requests from ``--seed``.

Every seed gets the same schedule: prompt lengths, output lengths and
inter-arrival gaps are stratified quantiles of the mix's distributions,
put in one fixed order (shuffled by the constant ``ORDER``), and the seed
draws only the token ids (and, in ``model.py``, the weights).  Shuffling
the order by the seed as well changed the work: near the knee, where long
prompts fall next to each other decides the queue, and six seeds' TTFT
medians spread over a factor of 2.6 while two runs of one seed agreed
within 2%.

Arrivals are open-loop: ``n = round(rate * seconds)`` requests due in
``[0, seconds)`` on the engine's clock, whatever the system does with them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Spec:
    """One generated request: ``arrival_s`` on the engine clock."""

    uid: int
    arrival_s: float
    prompt: np.ndarray  # (n,) int32
    max_new_tokens: int


ORDER = 0  # the one arrangement of the schedule, the same for every seed


def _rng(seed: int, stream: int) -> np.random.Generator:
    # seeds above 32 bits are fine: SeedSequence takes any non-negative int
    return np.random.default_rng([abs(int(seed)), stream])


def _strata(n: int) -> np.ndarray:
    """Mid-points of n equal probability strata: (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Standard normal quantiles (Acklam's rational approximation, relative
    error under 1.2e-9 — plenty for drawing lengths)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    return out


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, in increasing order."""
    p = _strata(n)
    kind = dist["dist"]
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        x = lo + p * (hi - lo)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + p * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * _norm_ppf(p))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gamma_ppf(p: np.ndarray, shape: float) -> np.ndarray:
    """Quantiles of a unit-mean gamma, interpolated in its CDF, which is
    integrated numerically (no scipy here)."""
    # the unit-mean gamma's CDF on a log grid, by the trapezoid rule on the
    # density in log space (the density diverges at 0 for shape < 1)
    scale = 1.0 / shape
    t = np.linspace(math.log(1e-12), math.log(60.0 / shape + 60.0), 200001)
    x = np.exp(t)
    logpdf = ((shape - 1) * np.log(x) - x / scale - math.lgamma(shape)
              - shape * math.log(scale))
    dens = np.exp(logpdf + t)  # d/dt
    cdf = np.concatenate([[0.0], np.cumsum((dens[1:] + dens[:-1]) / 2
                                           * np.diff(t))])
    cdf /= cdf[-1]
    return np.interp(p, cdf, x)


def gaps(arrival: dict, n: int) -> np.ndarray:
    """n stratified unit-mean inter-arrival gaps of the arrival process."""
    p = _strata(n)
    kind = arrival["process"]
    if kind == "poisson":
        g = -np.log1p(-p)
    elif kind == "gamma":
        cv = float(arrival["cv"])
        g = _gamma_ppf(p, 1.0 / (cv * cv))
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return g / g.mean()


def generate(mix: dict, *, seed: int, seconds: float,
             vocab_size: int) -> list[Spec]:
    """The requests due in ``[0, seconds)`` for one run of ``mix``."""
    n = max(1, int(round(float(mix["arrival"]["rate_per_s"]) * seconds)))
    rng = _rng(ORDER, 0)
    plen = rng.permutation(lengths(mix["prompt_len"], n))
    olen = rng.permutation(lengths(mix["output_len"], n))
    g = rng.permutation(gaps(mix["arrival"], n))
    # n gaps span the window: the first request is due at 0, and the last
    # gap ends at ``seconds``
    arrivals = np.concatenate([[0.0], np.cumsum(g)[:-1]]) * (seconds / n)
    tok = _rng(seed, 1)
    return [Spec(uid=i, arrival_s=float(arrivals[i]),
                 prompt=tok.integers(0, vocab_size, int(plen[i]),
                                     dtype=np.int32),
                 max_new_tokens=int(olen[i]))
            for i in range(n)]


def warmup_lengths(mix: dict, *, rung, blocks) -> list[int]:
    """One prompt length for every distinct compiled shape the mix's prompt
    range reaches: ``rung(n)`` is the KV-buffer depth the engine gives a
    prompt of n tokens and ``blocks(n)`` the block count of its admitted
    cache; both select programs."""
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    seen: dict = {}
    for n in range(lo, hi + 1):
        seen.setdefault((rung(n), blocks(n)), n)
    return sorted(seen.values())
