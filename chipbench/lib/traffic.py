"""The one traffic generator: a mix file of parameters plus ``--seed``
give the requests of a run.

Every seed gets the same work, so that runs with different seeds measure
the same thing.  The order of arrivals is part of that work: on a TPU v5e
at 0.8x the knee, seeds that shuffled the same arrival spacings spread
the SD3.5 cell's 90th percentile by 70%, while two runs of one seed
agreed to 0.3%.

* ``companyx`` mixes take a fixed slice of the CompanyX-like trace (the
  mix's ``trace.seed``), starting mid-trace, and ``--seed`` relabels its
  objects, so every object's latent differs.  Open-loop arrivals are a
  Poisson process at the mix's rate, conditioned on
  ``round(rate * seconds)`` arrivals in the window, drawn from the mix's
  ``arrivals.fixed_seed``: the same schedule for every seed.
* ``scan`` mixes read a corpus of ``corpus`` objects in order, wrapping;
  ``--seed`` relabels the corpus and picks where the scan starts.

``prefix_requests`` requests are served before the window (closed loop,
``window`` ids per call) to fill the caches; that is set-up.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from lib import companyx
from lib.weights import subseed

#: Ids generated per window second beyond the open-loop need (closed
#: loops read as fast as the system serves, so they get headroom).
CLOSED_IDS_PER_S = 1000


@dataclasses.dataclass
class Traffic:
    loop: str                       # "open" | "closed"
    window: int                     # most ids per get_many call
    prefix: np.ndarray              # served before the window
    ids: np.ndarray                 # window requests, in order
    due_s: Optional[np.ndarray]     # open loop: due offsets from the start
    objects: np.ndarray             # every object the run references


def _relabel(ids: np.ndarray, n_objects: int, seed: int) -> np.ndarray:
    perm = np.random.default_rng(subseed(seed, "relabel")).permutation(
        n_objects)
    return perm[ids]


def _open_arrivals(rate: float, seconds: float,
                   fixed_seed: int) -> np.ndarray:
    n = int(round(rate * seconds))
    e = np.random.default_rng(fixed_seed).exponential(1.0, n + 1)
    return (np.cumsum(e) / e.sum() * seconds)[:n]


def make_traffic(mix: Dict, seed: int, seconds: float) -> Traffic:
    gen = mix["generator"]
    prefix_n = int(mix.get("prefix_requests", 0))
    window = int(mix["window"])
    if mix["loop"] == "open":
        arr = mix["arrivals"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        due = _open_arrivals(float(arr["rate_per_s"]), seconds,
                             int(arr["fixed_seed"]))
        n_window = len(due)
    elif mix["loop"] == "closed":
        due = None
        n_window = int(CLOSED_IDS_PER_S * seconds) + window
    else:
        raise ValueError(f"loop must be open or closed: {mix['loop']!r}")

    if gen == "companyx":
        t = mix["trace"]
        n_obj = int(t["n_objects"])
        trace = companyx.object_ids(
            n_obj, n_obj * int(t["requests_per_object"]),
            float(t["span_days"]), float(t["zipf_alpha"]),
            float(t["decay_a0_days"]), float(t["decay_beta"]),
            float(t["birth_growth"]), float(t["burst_frac"]),
            float(t["burst_scale_s"]), int(t["seed"]))
        start = int(len(trace) * float(mix["start_fraction"]))
        seq = trace[start:start + prefix_n + n_window]
        if len(seq) < prefix_n + n_window:
            raise ValueError("the trace is too short for this window")
        seq = _relabel(seq, n_obj, seed)
    elif gen == "scan":
        corpus = int(mix["corpus"])
        first = np.random.default_rng(subseed(seed, "scan")).integers(corpus)
        seq = (first + np.arange(prefix_n + n_window)) % corpus
        seq = _relabel(seq, corpus, seed)
    else:
        raise ValueError(f"unknown generator {gen!r}")
    seq = seq.astype(np.int64)
    return Traffic(mix["loop"], window, seq[:prefix_n], seq[prefix_n:],
                   due, np.unique(seq))
