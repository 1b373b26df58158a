"""Input generators of the benchmark: document lengths, documents,
code archives, planted labels and arrival schedules, all from a seed.

Numpy only, so the load generator (a child process that never imports
JAX) can use it.  Every generator keeps the amount of work fixed across
seeds: lengths and arrival gaps are a fixed set of quantiles, and the
seed only picks their order and the feature ids.  Two runs with
different seeds therefore do the same work in another order.
"""
from __future__ import annotations

import math

import numpy as np

ID_SPACE = 1 << 30          # expanded rcv1: D ~ 2^30 features


def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def capped_lognormal_mean(mu: float, sigma: float, cap: float) -> float:
    """E[min(X, cap)] for X lognormal(mu, sigma)."""
    lc = math.log(cap)
    body = math.exp(mu + sigma * sigma / 2.0) * _phi((lc - mu - sigma * sigma)
                                                     / sigma)
    return body + cap * (1.0 - _phi((lc - mu) / sigma))


def fit_sigma(median: float, mean: float, cap: float) -> float:
    """The lognormal sigma whose length, capped at ``cap``, has the
    given mean; mu = ln(median) (the cap sits far above the median)."""
    mu = math.log(median)
    lo, hi = 1e-3, 6.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if capped_lognormal_mean(mu, mid, cap) < mean:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9; lengths are rounded to integers anyway)."""
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
    p = np.asarray(p, dtype=np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    return out


def length_set(n: int, median: float, mean: float, cap: int) -> np.ndarray:
    """The fixed multiset of ``n`` document lengths: the midpoint
    quantiles of lognormal(ln median, sigma), capped, at least 1, with
    sigma fitted so that the capped mean is ``mean``."""
    sigma = fit_sigma(median, mean, cap)
    p = (np.arange(n, dtype=np.float64) + 0.5) / n
    x = np.exp(math.log(median) + sigma * _norm_ppf(p))
    return np.clip(np.rint(x), 1, cap).astype(np.int64)


def shuffled(values: np.ndarray, seed: int, stream: int) -> np.ndarray:
    """``values`` in an order drawn from (seed, stream)."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, stream)))
    return values[rng.permutation(len(values))]


def doc_ids(rng: np.random.Generator, length: int) -> np.ndarray:
    """``length`` distinct feature ids, uniform over the id space: one
    id drawn uniformly in each of ``length`` equal strata."""
    stride = ID_SPACE // length
    base = np.arange(length, dtype=np.int64) * stride
    return base + rng.integers(0, stride, size=length, dtype=np.int64)


def serve_doc(seed: int, i: int, length: int) -> np.ndarray:
    """Request ``i``'s document: a pure function of (seed, i, length),
    so the checker regenerates any request without a copy."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, 7, i)))
    return doc_ids(rng, int(length))


def random_codes(seed: int, n: int, k: int, b: int) -> np.ndarray:
    """(n, k) b-bit codes, uniform over [0, 2^b), as uint16."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, 3)))
    return rng.integers(0, 1 << b, size=(n, k), dtype=np.uint16)


def planted_labels(seed: int, codes: np.ndarray, b: int,
                   noise: float) -> np.ndarray:
    """Labels of a planted linear model over the one-hot expansion:
    y = [sum_j w[j, code_j] / sqrt(k) + noise * eps > 0], w ~ N(0, 1)."""
    n, k = codes.shape
    rng = np.random.default_rng(np.random.SeedSequence((seed, 5)))
    w = rng.standard_normal((k, 1 << b)).astype(np.float32)
    margin = np.zeros(n, dtype=np.float32)
    step = 1 << 15
    cols = np.arange(k)[None, :]
    for lo in range(0, n, step):
        margin[lo:lo + step] = w[cols, codes[lo:lo + step]].sum(axis=1)
    margin /= np.float32(math.sqrt(k))
    margin += np.float32(noise) * rng.standard_normal(n).astype(np.float32)
    return (margin > 0).astype(np.int32)


def arrival_offsets(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Open-loop send times in [0, seconds): the ``rate * seconds``
    midpoint quantiles of the exponential gap, in a seeded order.  Every
    seed sends the same number of requests over the same span."""
    n = max(1, int(round(rate * seconds)))
    p = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-p) / rate
    gaps *= seconds / gaps.sum()
    gaps = shuffled(gaps, seed, 13)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


_DIGITS4 = np.frombuffer("".join(f"{i:04d}" for i in range(10000)).encode(),
                         dtype="<u4")
_TENS = np.array([10 ** p for p in range(1, 10)], dtype=np.int64)


def ids_json(ids: np.ndarray) -> bytes:
    """Comma-separated decimal ids (below 2^31), without brackets, built
    in numpy: ``json.dumps`` is too slow for tens of millions of ids."""
    a = np.asarray(ids, dtype=np.int64)
    if a.size == 0:
        return b""
    u = a.astype(np.uint32)
    words = np.empty((a.size, 4), dtype="<u4")     # 12 digits, ",", pad
    words[:, 0] = _DIGITS4[u // np.uint32(10 ** 8)]
    words[:, 1] = _DIGITS4[(u // np.uint32(10 ** 4)) % np.uint32(10 ** 4)]
    words[:, 2] = _DIGITS4[u % np.uint32(10 ** 4)]
    words[:, 3] = ord(",")
    nd = np.searchsorted(_TENS, a, side="right") + 1
    col = np.arange(16)[None, :]
    keep = (col >= (12 - nd)[:, None]) & (col <= 12)
    return words.view(np.uint8).reshape(a.size, 16)[keep].tobytes()[:-1]
