"""A copy of the CompanyX-like access trace generator (``companyx``
scenario) of the system under test, so that later changes to the program
cannot change the benchmark's traffic.

Objects are born over the span with slowly growing intensity; each gets a
Zipf lifetime weight (alpha 1.11 by default) and its accesses are placed
at post-birth ages drawn from a truncated Lomax decay; a share of the
re-accesses are bursty follow-ups of the previous access.  Returns object
ids in time order.
"""

from __future__ import annotations

import numpy as np

DAY_S = 86_400.0


def _zipf_weights(n, alpha, rng):
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    rng.shuffle(w)
    return w / w.sum()


def _births(n, span_s, growth, rng):
    u = rng.random(n)
    if growth <= 1e-9:
        return u * span_s
    a = growth / (2.0 * span_s)
    c = -u * span_s * (1.0 + growth / 2.0)
    t = (-1.0 + np.sqrt(1.0 - 4.0 * a * c)) / (2.0 * a)
    return np.clip(t, 0.0, span_s)


def _lomax_trunc(a0_s, beta, max_age_s, rng):
    fmax = 1.0 - (1.0 + np.maximum(max_age_s, 0.0) / a0_s) ** (1.0 - beta)
    u = rng.random(len(max_age_s)) * fmax
    a = a0_s * ((1.0 - u) ** (1.0 / (1.0 - beta)) - 1.0)
    return np.clip(a, 0.0, max_age_s)


def object_ids(n_objects: int, n_requests: int, span_days: float,
               zipf_alpha: float, decay_a0_days: float, decay_beta: float,
               birth_growth: float, burst_frac: float, burst_scale_s: float,
               seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    span_s = span_days * DAY_S
    births = _births(n_objects, span_s, birth_growth, rng)
    weights = _zipf_weights(n_objects, zipf_alpha, rng)
    frac_life = 1.0 - (1.0 + (span_s - births)
                       / (decay_a0_days * DAY_S)) ** (1.0 - decay_beta)
    eff = weights * frac_life
    counts = rng.poisson(n_requests * eff / eff.sum())
    total = int(counts.sum())
    oid = np.repeat(np.arange(n_objects, dtype=np.int64), counts)
    birth_of = np.repeat(births, counts)
    ts = birth_of + _lomax_trunc(decay_a0_days * DAY_S, decay_beta,
                                 span_s - birth_of, rng)
    order = np.lexsort((ts, oid))
    ts_o, oid_o = ts[order], oid[order]
    same_prev = np.zeros(total, dtype=bool)
    same_prev[1:] = oid_o[1:] == oid_o[:-1]
    burst = same_prev & (rng.random(total) < burst_frac)
    delta = rng.exponential(burst_scale_s, size=total)
    ts_new = ts_o.copy()
    idx = np.nonzero(burst)[0]
    ts_new[idx] = ts_o[idx - 1] + delta[idx]
    ts_new = np.minimum(ts_new, span_s)
    return oid_o[np.argsort(ts_new, kind="stable")]
