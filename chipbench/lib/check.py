"""How ``correct`` is decided: served images against the plain reference.

After the window has closed, a sample of the served requests, drawn from
``--seed``, is decoded by the reference from the latents the benchmark
made (not from bytes read back from the store), and each served payload
is compared with the reference's uint8 image of the object it was asked
for.  The sample takes one request of every (hit class, decode bucket)
pair the window served before it fills up at random, so pixel-cache hits,
latent hits, full misses and every batch bucket are covered.  Every
served payload is also checked to be a uint8 image of the right shape
for the id it answers.

Numbers compared, each with its limit from the configuration:

* ``mismatch_ppm``: the share of the sample's subpixels that differ from
  the reference at all, per million;
* ``bad_payloads``: served answers that are not the requested object's
  uint8 image of the right shape (exact: limit 0).

The largest |served - reference| over the sample (``max_lsb``) is
reported beside them but not compared: the control (bf16 weights) reads
2 LSB where sound runs read 1, too close to set a limit between.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from lib.weights import latent, subseed

DECODE_CLASSES = ("latent_hit", "full_miss", "regen_miss")


def bucket_labels(window, buckets: Sequence[int]) -> List[Optional[int]]:
    """The decode bucket each served request's image came from, where the
    call's counters pin it down (None otherwise, and for image hits).

    Within a call the batcher decodes the unique ids of its decoding
    requests in admission order, in chunks of the largest bucket, each
    padded to the smallest bucket that holds it."""
    buckets = sorted(buckets)
    labels: List[Optional[int]] = [None] * len(window.results)
    for call in window.calls:
        order: List[int] = []
        where: Dict[int, List[int]] = {}
        for k in range(call.first, call.first + call.n):
            r = window.results[k]
            if r is None or r.hit_class not in DECODE_CLASSES:
                continue
            if r.oid not in where:
                order.append(r.oid)
            where.setdefault(r.oid, []).append(k)
        if len(order) != call.batcher["decodes"]:
            continue
        top = buckets[-1]
        for s in range(0, len(order), top):
            chunk = order[s:s + top]
            b = next((b for b in buckets if b >= len(chunk)), len(chunk))
            for oid in chunk:
                for k in where[oid]:
                    labels[k] = b
    return labels


def choose_sample(window, buckets: Sequence[int], seed: int,
                  k: int) -> List[int]:
    served = [i for i, r in enumerate(window.results) if r is not None]
    rng = np.random.default_rng(subseed(seed, "sample"))
    order = [served[j] for j in rng.permutation(len(served))]
    labels = bucket_labels(window, buckets)
    picked, strata = [], set()
    for i in order:
        stratum = (window.results[i].hit_class, labels[i])
        if stratum not in strata:
            strata.add(stratum)
            picked.append(i)
    for i in order:
        if len(picked) >= k:
            break
        if i not in picked:
            picked.append(i)
    return sorted(picked)


def bad_payloads(window, shape: Tuple[int, int, int]) -> int:
    bad = 0
    for want, r in zip(window.ids, window.results):
        if r is None:
            continue
        p = r.payload
        if (int(r.oid) != int(want) or p is None or p.dtype != np.uint8
                or tuple(p.shape) != tuple(shape)):
            bad += 1
    return bad


def compare(window, sample: List[int], reference, seed: int,
            latent_hwc: Tuple[int, int, int]) -> Dict[str, float]:
    """``reference(z [h, w, C] fp32) -> uint8 image``, run once per
    object of the sample."""
    refs: Dict[int, np.ndarray] = {}
    worst, differ, total = 0, 0, 0
    for i in sample:
        r = window.results[i]
        oid = int(window.ids[i])
        if oid not in refs:
            refs[oid] = reference(latent(seed, oid, latent_hwc)
                                  .astype(np.float32))
        want = refs[oid]
        got = r.payload
        if got is None or got.shape != want.shape:
            continue                     # counted by bad_payloads
        d = np.abs(got.astype(np.int16) - want.astype(np.int16))
        worst = max(worst, int(d.max()))
        differ += int(np.count_nonzero(d))
        total += d.size
    return {"max_lsb": float(worst),
            "mismatch_ppm": 1e6 * differ / total if total else 0.0,
            "compared_images": float(len(sample)),
            "compared_objects": float(len(refs))}
