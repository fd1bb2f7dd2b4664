"""Persistent Pallas kernel autotuner: per-shape block/band sweeps with a
versioned on-disk tuning cache.

The decode-path kernels take two blocking knobs — ``rows`` (the VMEM row
band) and ``block_cout`` (the output-channel tile) — whose hand-picked
defaults are right on average and wrong per shape: the best band for a
128-wide 512-channel mid-block tile is not the best band for a 512-wide
32-channel top level.  This module closes that gap:

* :func:`decode_shapes` derives, from a :class:`repro.vae.model.VAEConfig`
  + latent shape + batch bucket, the exact ``(kernel, call shape)`` set the
  ``decode_u8`` fast path will dispatch;
* :func:`tune` sweeps each shape's candidate grid with a timed best-of-N
  harness (injectable ``timer`` for deterministic tests; candidates that
  clamp to the same effective blocking are deduplicated, and the default
  config is always candidate 0 — so the winner can never be *worse* than
  the default under the measurements taken);
* :class:`TuningCache` persists winners to ``tuning_cache.json`` under the
  store's ``data_dir`` — schema-versioned, written atomically
  (tmp + rename), and loaded with a clean fall-back-to-defaults on a
  missing, corrupt, or stale-version file;
* ``ops.py`` dispatch consults the process-wide *active* cache
  (:func:`set_active_cache`) on every Pallas call, so ``prewarm_decode``
  compiles the tuned shapes;
* :class:`KernelAutotuner` is the serving-side driver: the engine notes
  each (bucket, latent shape) it decodes, and ``step(budget)`` tunes a
  bounded number of missing keys per call — tune-on-first-miss threaded
  into the engine's end-of-batch maintenance, so cold clusters converge
  without a manual step.

Offline pre-tuning: ``python -m repro.kernels.autotune --cache PATH``
(``--smoke`` for the CI grid); point ``StoreConfig.data_dir`` at the same
directory and every reopen picks the winners up.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.conv3x3 import band_rows, cout_tile, plan

SCHEMA_VERSION = 1
CACHE_FILENAME = "tuning_cache.json"

#: Kernels the tuner knows how to drive (the decode_u8 dispatch set).
KERNELS = ("conv3x3", "gn_silu_conv3x3", "upsample_conv3x3",
           "output_epilogue")

#: Hand-picked dispatch defaults (must mirror the kernel wrappers'
#: keyword defaults — candidate 0 of every sweep).
DEFAULTS = {
    "conv3x3": {"rows": 32, "block_cout": 128},
    "gn_silu_conv3x3": {"rows": 32, "block_cout": 128},
    "upsample_conv3x3": {"rows": 16, "block_cout": 128},
    "output_epilogue": {"rows": 32, "block_cout": 128},
    "rms_output_epilogue": {"rows": 32, "block_cout": 128},
}

#: The banded-conv kernels whose band carries the fused GroupNorm scratch
#: (sized into their VMEM plan).
GN_FUSED = ("gn_silu_conv3x3", "output_epilogue")

_ROWS_GRID = (8, 16, 32, 64)
_BLOCK_COUT_GRID = (32, 64, 128, 256)


def cache_key(kernel: str, n: int, h: int, w: int, cin: int, cout: int,
              weight_dtype: str) -> str:
    """One tuning-cache key per (kernel, resolution, bucket, weight_dtype)."""
    return f"{kernel}|n{n}|{h}x{w}|{cin}->{cout}|{weight_dtype}"


# ---------------------------------------------------------------------------
# the persistent cache
# ---------------------------------------------------------------------------

class TuningCache:
    """Versioned JSON map ``cache_key -> {'rows', 'block_cout', ...}``.

    Loading never raises on bad files: a missing, unparseable, or
    wrong-``schema_version`` file yields an *empty* cache (the kernels
    then run on their hand-picked defaults), so a stale cache from an
    older code revision can degrade performance only back to the
    defaults, never correctness.  Writes go through a tmp file +
    ``os.replace`` so a crash mid-save leaves the previous cache intact.
    """

    def __init__(self, path: Optional[str] = None,
                 entries: Optional[Dict[str, Dict[str, Any]]] = None):
        self.path = path
        self.entries: Dict[str, Dict[str, Any]] = dict(entries or {})

    @classmethod
    def load(cls, path: Optional[str]) -> "TuningCache":
        cache = cls(path)
        if path is None or not os.path.exists(path):
            return cache
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            if (isinstance(doc, dict)
                    and doc.get("schema_version") == SCHEMA_VERSION
                    and isinstance(doc.get("entries"), dict)):
                cache.entries = {
                    str(k): dict(v) for k, v in doc["entries"].items()
                    if isinstance(v, dict)}
        except (OSError, ValueError):
            pass                        # corrupt file -> clean empty cache
        return cache

    def save(self) -> None:
        if self.path is None:
            return
        doc = {"schema_version": SCHEMA_VERSION,
               "jax_backend": jax.default_backend(),
               "entries": self.entries}
        tmp = self.path + ".tmp"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        return self.entries.get(key)

    def put(self, key: str, entry: Dict[str, Any]) -> None:
        self.entries[key] = dict(entry)

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: str) -> bool:
        return key in self.entries


_ACTIVE: Optional[TuningCache] = None


def set_active_cache(cache: Optional[TuningCache]) -> None:
    """Install the process-wide cache ``ops.py`` dispatch consults
    (models never thread it explicitly)."""
    global _ACTIVE
    _ACTIVE = cache


def get_active_cache() -> Optional[TuningCache]:
    return _ACTIVE


@contextlib.contextmanager
def active_cache(cache: Optional[TuningCache]):
    """Scoped :func:`set_active_cache` (benches/tests)."""
    prev = _ACTIVE
    set_active_cache(cache)
    try:
        yield cache
    finally:
        set_active_cache(prev)


def tuned_params(kernel: str, x_shape: Sequence[int], cout: int,
                 weight_dtype: str) -> Dict[str, int]:
    """The dispatch-side lookup: tuned ``{'rows', 'block_cout'}`` for this
    call, or ``{}`` (kernel defaults) on no active cache / cache miss /
    malformed entry.  Runs at trace time only (inside ``jax.jit`` the
    shapes are static)."""
    if _ACTIVE is None:
        return {}
    n, h, w, cin = x_shape
    entry = _ACTIVE.get(cache_key(kernel, n, h, w, cin, cout, weight_dtype))
    if not entry:
        return {}
    out = {}
    for knob in ("rows", "block_cout"):
        v = entry.get(knob)
        if isinstance(v, int) and v > 0:
            out[knob] = v
    return out if len(out) == 2 else {}


# ---------------------------------------------------------------------------
# shape derivation (what will decode_u8 actually dispatch?)
# ---------------------------------------------------------------------------

def decode_shapes(cfg, latent_hwc: Tuple[int, int, int],
                  bucket: int) -> List[Dict[str, Any]]:
    """The deduplicated ``(kernel, call shape)`` set of one ``decode_u8``
    at batch size ``bucket`` — derived from the decoder architecture, not
    traced, so it can run before any compile.  ``cfg`` is a
    :class:`repro.vae.model.VAEConfig`; a DC-AE decoder is refused (its
    decode runs other kernels, and none of them is tuned)."""
    from repro.vae.dcae import DCAEConfig
    if isinstance(cfg, DCAEConfig):
        raise ValueError(f"decode_shapes: {cfg.name!r} is a DC-AE decoder; "
                         f"the walk covers AutoencoderKL decoders only")
    h, w, c_lat = (int(v) for v in latent_hwc)
    n = int(bucket)
    chs = cfg.block_out_channels
    top = chs[-1]
    shapes: List[Dict[str, Any]] = []
    seen = set()

    def add(kernel, h_, w_, cin, cout):
        spec = {"kernel": kernel, "n": n, "h": h_, "w": w_,
                "cin": cin, "cout": cout, "groups": cfg.groups}
        sig = (kernel, h_, w_, cin, cout)
        if sig not in seen:
            seen.add(sig)
            shapes.append(spec)

    add("conv3x3", h, w, c_lat, top)                 # conv_in
    add("gn_silu_conv3x3", h, w, top, top)           # mid res blocks
    cin = top
    for i, cout in enumerate(reversed(chs)):
        for _ in range(cfg.layers_per_block + 1):
            add("gn_silu_conv3x3", h, w, cin, cout)
            cin = cout
        if i < len(chs) - 1:
            add("upsample_conv3x3", h, w, cout, cout)
            h, w = 2 * h, 2 * w
    add("output_epilogue", h, w, chs[0], cfg.image_channels)
    return shapes


# ---------------------------------------------------------------------------
# candidate grids + the timed harness
# ---------------------------------------------------------------------------

def _effective(kernel: str, spec: Dict[str, Any], rows: int,
               block_cout: int, itemsize: int = 4) -> Tuple[int, int]:
    """The (band rows, cout tile) a candidate actually compiles to —
    mirrors the wrappers' clamping, so candidates that collapse to the
    same blocking are swept once."""
    h, w, cin, cout = spec["h"], spec["w"], spec["cin"], spec["cout"]
    if kernel != "upsample_conv3x3":
        return plan((1, h, w, cin), cout, itemsize, rows, block_cout,
                    kernel in GN_FUSED)[:2]
    tc = cout_tile(cout, block_cout)
    return band_rows(h, w, cin, itemsize, rows, tc=tc, taps=8,
                     out_factor=2), tc


def candidates(kernel: str, spec: Dict[str, Any],
               rows_grid: Sequence[int] = _ROWS_GRID,
               block_cout_grid: Sequence[int] = _BLOCK_COUT_GRID,
               ) -> List[Dict[str, int]]:
    """Deduplicated candidate list; the kernel's default config is always
    candidate 0 (ties in the sweep resolve to the earliest candidate, so
    'no measurable win' keeps the default)."""
    default = DEFAULTS[kernel]
    out: List[Dict[str, int]] = []
    seen = set()
    for cand in ([default]
                 + [{"rows": r, "block_cout": bc}
                    for r in rows_grid for bc in block_cout_grid]):
        eff = _effective(kernel, spec, cand["rows"], cand["block_cout"])
        if eff not in seen:
            seen.add(eff)
            out.append(dict(cand))
    return out


def _make_inputs(spec: Dict[str, Any], weight_dtype: str, seed: int = 0):
    """Deterministic synthetic operands for one kernel call."""
    rng = np.random.default_rng(seed)
    h, w, cin, cout = spec["h"], spec["w"], spec["cin"], spec["cout"]
    x = jnp.asarray(rng.standard_normal((spec["n"], h, w, cin)), jnp.float32)
    wf = rng.standard_normal((3, 3, cin, cout)).astype(np.float32)
    wf /= np.sqrt(9 * cin)
    b = jnp.asarray(rng.standard_normal((cout,)) * 0.01, jnp.float32)
    w_scale = None
    if weight_dtype == "bfloat16":
        wk = jnp.asarray(wf).astype(jnp.bfloat16)
    elif weight_dtype == "int8":
        from repro.vae.quantize import quantize_int8   # lazy: no cycle
        qw = quantize_int8(jnp.asarray(wf))
        wk, w_scale = qw.q, qw.scale
    else:
        wk = jnp.asarray(wf)
    gscale = jnp.ones((cin,), jnp.float32)
    gbias = jnp.zeros((cin,), jnp.float32)
    return x, wk, b, w_scale, gscale, gbias


def _make_thunk(spec: Dict[str, Any], weight_dtype: str, impl: str,
                cand: Dict[str, int]) -> Callable[[], Any]:
    """A zero-arg callable running one kernel at one candidate config."""
    from repro.kernels import (conv3x3 as c3, gn_silu_conv as gsc,
                               output_epilogue as oe, upsample_conv as uc)
    kernel = spec["kernel"]
    interp = impl == "pallas_interpret"
    x, wk, b, w_scale, gscale, gbias = _make_inputs(spec, weight_dtype)
    kw = dict(rows=cand["rows"], block_cout=cand["block_cout"],
              interpret=interp, w_scale=w_scale)
    if kernel == "conv3x3":
        return lambda: c3.conv3x3(x, wk, b, **kw)
    if kernel == "upsample_conv3x3":
        return lambda: uc.upsample_conv3x3(x, wk, b, **kw)
    if kernel == "gn_silu_conv3x3":
        return lambda: gsc.gn_silu_conv3x3(x, gscale, gbias, wk, b,
                                           groups=spec["groups"], **kw)
    if kernel == "output_epilogue":
        return lambda: oe.output_epilogue(x, gscale, gbias, wk, b,
                                          groups=spec["groups"], **kw)
    raise ValueError(f"unknown kernel {kernel!r} (valid: {KERNELS})")


def time_call(thunk: Callable[[], Any], reps: int = 2,
              timer: Callable[[], float] = time.perf_counter) -> float:
    """Best-of-N wall time in microseconds.  One untimed warmup call pays
    the compile; then exactly 2 ``timer()`` reads per rep (a scripted fake
    timer makes winner selection fully deterministic in tests)."""
    jax.block_until_ready(thunk())
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = timer()
        jax.block_until_ready(thunk())
        best = min(best, timer() - t0)
    return best * 1e6


def tune(spec: Dict[str, Any], weight_dtype: str = "float32",
         impl: str = "pallas_interpret", reps: int = 2,
         timer: Callable[[], float] = time.perf_counter,
         rows_grid: Sequence[int] = _ROWS_GRID,
         block_cout_grid: Sequence[int] = _BLOCK_COUT_GRID,
         ) -> Dict[str, Any]:
    """Sweep one shape's candidate grid; returns the cache entry.

    The default config is always measured (candidate 0) and ties break
    toward it, so ``entry['us'] <= entry['default_us']`` by construction
    under the harness's own measurements."""
    cands = candidates(spec["kernel"], spec, rows_grid, block_cout_grid)
    best_i, best_us, default_us = 0, float("inf"), None
    for i, cand in enumerate(cands):
        us = time_call(_make_thunk(spec, weight_dtype, impl, cand),
                       reps=reps, timer=timer)
        if i == 0:
            default_us = us
        if us < best_us:
            best_i, best_us = i, us
    return {"rows": cands[best_i]["rows"],
            "block_cout": cands[best_i]["block_cout"],
            "us": best_us, "default_us": default_us,
            "candidates": len(cands), "impl": impl,
            "weight_dtype": weight_dtype}


# ---------------------------------------------------------------------------
# serving-side driver: tune-on-first-miss
# ---------------------------------------------------------------------------

class KernelAutotuner:
    """Bounded background tuner the :class:`ServingEngine` drives.

    ``note_bucket`` records a (bucket, latent shape) the engine is
    decoding and queues every derived kernel shape missing from the
    cache; ``step(budget)`` tunes at most ``budget`` queued keys (one
    engine maintenance slice = one key by default) and persists the cache
    after each batch of wins.  Tuning runs the kernels *standalone* — by
    default in ``pallas_interpret`` off-TPU — so the serving decode path
    itself never blocks on a sweep.
    """

    def __init__(self, cache: TuningCache, vae_cfg,
                 weight_dtype: str = "float32", impl: Optional[str] = None,
                 reps: int = 2,
                 timer: Callable[[], float] = time.perf_counter,
                 rows_grid: Sequence[int] = _ROWS_GRID,
                 block_cout_grid: Sequence[int] = _BLOCK_COUT_GRID):
        if impl is None:
            impl = ("pallas" if jax.default_backend() == "tpu"
                    else "pallas_interpret")
        self.cache = cache
        self.vae_cfg = vae_cfg
        self.weight_dtype = weight_dtype
        self.impl = impl
        self.reps = reps
        self.timer = timer
        self.rows_grid = tuple(rows_grid)
        self.block_cout_grid = tuple(block_cout_grid)
        self._queue: List[Tuple[str, Dict[str, Any]]] = []
        self._queued: set = set()

    @property
    def pending(self) -> int:
        return len(self._queue)

    def note_bucket(self, bucket: int,
                    latent_hwc: Tuple[int, int, int]) -> int:
        """Queue every kernel shape of this (bucket, latent) decode that
        the cache doesn't cover yet; returns how many were enqueued."""
        added = 0
        for spec in decode_shapes(self.vae_cfg, latent_hwc, bucket):
            key = cache_key(spec["kernel"], spec["n"], spec["h"], spec["w"],
                            spec["cin"], spec["cout"], self.weight_dtype)
            if key in self.cache or key in self._queued:
                continue
            self._queued.add(key)
            self._queue.append((key, spec))
            added += 1
        return added

    def step(self, budget: int = 1) -> List[str]:
        """Tune up to ``budget`` queued keys; persists the cache if any
        were tuned and returns their keys (callers re-warm the decode so
        new compilations land outside timed serving regions)."""
        tuned: List[str] = []
        while self._queue and len(tuned) < budget:
            key, spec = self._queue.pop(0)
            entry = tune(spec, weight_dtype=self.weight_dtype,
                         impl=self.impl, reps=self.reps, timer=self.timer,
                         rows_grid=self.rows_grid,
                         block_cout_grid=self.block_cout_grid)
            self.cache.put(key, entry)
            tuned.append(key)
        if tuned:
            self.cache.save()
        return tuned


# ---------------------------------------------------------------------------
# offline pre-tuning CLI
# ---------------------------------------------------------------------------

def _cli_sweep(cache: TuningCache, vae_cfg, latent_hwc, buckets,
               weight_dtypes, impl, reps, rows_grid, block_cout_grid,
               verbose: bool = True) -> int:
    tuned = 0
    for wd in weight_dtypes:
        tuner = KernelAutotuner(cache, vae_cfg, weight_dtype=wd, impl=impl,
                                reps=reps, rows_grid=rows_grid,
                                block_cout_grid=block_cout_grid)
        for b in buckets:
            tuner.note_bucket(b, latent_hwc)
        while tuner.pending:
            for key in tuner.step(4):
                e = cache.get(key)
                tuned += 1
                if verbose:
                    speed = e["default_us"] / max(e["us"], 1e-9)
                    print(f"  {key}: rows={e['rows']} "
                          f"block_cout={e['block_cout']} "
                          f"{e['us']:.0f}us ({speed:.2f}x vs default)")
    return tuned


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="Offline Pallas kernel pre-tuner (persists winners to "
                    "a versioned tuning cache that StoreConfig.data_dir "
                    "picks up)")
    p.add_argument("--cache", default=os.path.join("artifacts",
                                                   CACHE_FILENAME))
    p.add_argument("--smoke", action="store_true",
                   help="tiny CI grid: demo decoder, buckets 1/2, "
                        "float32+bfloat16, 1 rep")
    p.add_argument("--impl", default=None,
                   choices=("pallas", "pallas_interpret"),
                   help="default: pallas on TPU, pallas_interpret elsewhere")
    p.add_argument("--buckets", type=int, nargs="+", default=None)
    p.add_argument("--latent", type=int, nargs=3, default=None,
                   metavar=("H", "W", "C"))
    p.add_argument("--weight-dtypes", nargs="+", default=None,
                   choices=("float32", "bfloat16", "int8"))
    p.add_argument("--reps", type=int, default=None)
    args = p.parse_args(argv)

    # the facade's demo decoder (LatentBox.engine default stack)
    from repro.vae.model import DEMO_VAE as vae_cfg
    impl = args.impl or ("pallas" if jax.default_backend() == "tpu"
                         else "pallas_interpret")
    if args.smoke:
        buckets = args.buckets or (1, 2)
        latent = tuple(args.latent or (8, 8, 4))
        wdtypes = args.weight_dtypes or ("float32", "bfloat16")
        reps = args.reps or 1
        rows_grid, bc_grid = (8, 16, 32), (32, 64, 128)
    else:
        buckets = args.buckets or (1, 2, 4, 8)
        latent = tuple(args.latent or (8, 8, 4))
        wdtypes = args.weight_dtypes or ("float32", "bfloat16", "int8")
        reps = args.reps or 3
        rows_grid, bc_grid = _ROWS_GRID, _BLOCK_COUT_GRID

    cache = TuningCache.load(args.cache)
    print(f"tuning {vae_cfg.name} decoder @ latent {latent}, "
          f"buckets {tuple(buckets)}, weight_dtypes {tuple(wdtypes)}, "
          f"impl={impl} ({len(cache)} cached entries loaded)")
    n = _cli_sweep(cache, vae_cfg, latent, buckets, wdtypes, impl, reps,
                   rows_grid, bc_grid)
    cache.save()
    print(f"tuned {n} new keys -> {args.cache} ({len(cache)} total)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
