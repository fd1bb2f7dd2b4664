"""The ENGINE backend of the LatentBox object-store API: real jitted
decode behind the shared tier-walk read path.

Since the store refactor there is exactly one read path —
:class:`repro.store.walk.TierWalk` (pixel cache -> latent cache -> durable
latent -> recipe regeneration) — and two backends of the same ``LatentBox``
facade: this module supplies *real compute* (jitted VAE decode, measured
wall-clock feeding the tuner EWMAs), while ``core/cluster.py`` supplies
*latency events* for the same walk.  ``ServingEngine`` keeps its direct
``get``/``get_many`` surface for existing callers/tests, but every
classification, admission, promotion, and spillover decision now comes from
the shared walk, so the engine can no longer drift from the simulator.

Serving is no longer window-only: ``admit``/``dispatch`` expose the open
microbatch directly, so the event-loop serving runtime
(``repro.serve.runtime``) can feed the ``DecodeBatcher`` *continuously* —
closing a batch when a size bucket fills or a queued deadline forces it —
while ``serve_window`` remains as the fixed-group path (admit-all then
dispatch) that the drain-mode conformance guarantee is defined against.
``serve_stream`` replays a timestamped open-loop request stream through
that runtime.

Misses do not decode one-by-one: they accumulate in a ``DecodeBatcher``
queue where duplicate in-flight object ids coalesce into a single decode
(single-flight), then flush as batches padded up to a small set of
bucketed batch sizes (default 1/2/4/8) so ``jax.jit`` compiles once per
bucket and latent shape instead of once per arrival pattern.  Per-image
wall-clock (batch time / real images in the batch) feeds the marginal-hit
tuner's EWMAs, closing the paper's feedback loop on real measurements.
Decode is deterministic per image and shape: a padded slot never changes
a real image's pixels, but batches of different size are different
compiled programs, so a bucketed decode agrees with a batch-1 decode of
the same latent to the uint8 contract of the whole fast path — within
±1 LSB — not bit for bit.

The read path records ``lb.*`` spans (``jax.profiler.TraceAnnotation``)
where its work happens: ``lb.serve_window`` around a call, ``lb.lookup``
per request (``lb.fetch`` for its durable read), ``lb.flush`` around the
batched decode with ``lb.assemble`` (``lb.decompress``, ``lb.warm_up``
with the banded convs its compile traced per kernel path and its
linear-attention kernel calls, ``lb.place``), ``lb.dispatch`` and
``lb.collect`` per chunk, and ``lb.writeback`` after it.  Spans of one call share the ``call`` stat,
those of one request its ``oid``.  They land in a profiler trace, on the
device's clock, only while one is being recorded
(``jax.profiler.start_trace``); otherwise each costs about a microsecond.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.compression.latentcodec import compress_latent, decompress_latent
from repro.core.dual_cache import IMAGE_HIT, LATENT_HIT
from repro.core.latent_store import LatentStore
from repro.core.regen_tier import Recipe, RegenTierStore, synthesize_image
from repro.core.router import parse_node_index
from repro.core.tuner import MarginalHitTuner, TunerConfig
from repro.kernels.conv3x3 import PATHS as conv_paths
from repro.kernels.linear_attention import TRACED as linattn_calls
from repro.store.api import StoreConfig
from repro.store.tiers import DurableTier, RecipeTier
from repro.store.walk import TierWalk
from repro.vae.model import VAE


@dataclasses.dataclass
class EngineConfig:
    n_nodes: int = 2
    cache_bytes_per_node: float = 64e6
    alpha0: float = 0.5
    tau: float = 0.1
    #: Paper parameter ``h``: latent hits before promotion to the pixel
    #: tier; doubles as the spillover queue-depth bound (the deprecated
    #: ``theta`` alias encoded the same value).
    promote_threshold: int = 4
    decode_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    #: 'uint8' serves displayable bytes straight off the fused decode
    #: epilogue (1/4 the transfer + pixel-cache charge); 'float32' keeps
    #: the legacy [-1, 1] float pixels.
    pixel_format: str = "uint8"
    #: Decoder weight storage precision for the uint8 fast path
    #: ('float32' | 'bfloat16' | 'int8'), applied behind the ±1-LSB
    #: open-time gate — see :class:`repro.store.api.StoreConfig`.
    weight_dtype: str = "float32"
    #: Persistent Pallas kernel autotuning (tune-on-first-miss; cache
    #: under ``data_dir``) — see :class:`repro.store.api.StoreConfig`.
    autotune: bool = False
    adaptive: bool = True               # run the marginal-hit tuner
    tuner: TunerConfig = dataclasses.field(
        default_factory=lambda: TunerConfig(window=500, step=0.02))
    #: Injectable wall clock (seconds): every engine-side ``now_s`` —
    #: notably the store-latency warmth draws — routes through it, so
    #: tests can pin or advance time deterministically.  ``None`` =
    #: ``time.time``.
    clock: Optional[Any] = None
    #: Deprecated alias of ``promote_threshold`` — passing it is an error.
    theta: dataclasses.InitVar[Optional[int]] = None

    def __post_init__(self, theta: Optional[int]) -> None:
        if theta is not None:
            raise TypeError(
                "EngineConfig.theta was merged into promote_threshold "
                "(both encode the paper's h); pass promote_threshold "
                "instead")

    def store_config(self, image_bytes: float,
                     latent_bytes: float) -> StoreConfig:
        """The cache/routing half of this config, for the shared walk."""
        return StoreConfig(
            n_nodes=self.n_nodes,
            cache_bytes_per_node=self.cache_bytes_per_node,
            alpha0=self.alpha0, tau=self.tau,
            promote_threshold=self.promote_threshold,
            image_bytes=image_bytes, latent_bytes=latent_bytes,
            adaptive=self.adaptive, tuner=self.tuner,
            decode_buckets=self.decode_buckets,
            pixel_format=self.pixel_format,
            weight_dtype=self.weight_dtype, autotune=self.autotune,
            clock=self.clock)


class _Node:
    """Engine-side view of one walk node: payload dicts + decode queue
    depth around the walk's cache/tuner."""

    def __init__(self, idx: int, tier) -> None:
        self.idx = idx
        self.tier = tier
        self.cache = tier.cache
        self.tuner: Optional[MarginalHitTuner] = tier.tuner
        self.images: Dict[int, np.ndarray] = {}     # decoded-image payloads
        self.latents: Dict[int, bytes] = {}         # compressed payloads
        self.queue_depth = 0

    def drop_payloads(self, oid: int) -> None:
        self.images.pop(oid, None)
        self.latents.pop(oid, None)


# legacy alias: the parser moved to core.router (the sharded cluster's
# global namespace relies on it too)
_node_index = parse_node_index


class DecodeBatcher:
    """Microbatching decode scheduler over one jitted VAE decode.

    Pending misses queue up via :meth:`submit`; duplicate in-flight object
    ids coalesce into one decode (single-flight).  :meth:`flush` drains the
    queue in FIFO order as batches, each padded up to the smallest
    configured bucket that fits so the jitted decode sees only
    ``len(buckets)`` distinct batch shapes per latent shape.  Padding
    repeats the last real latent — the decode is per-image independent,
    so padded slots never perturb the real outputs; across buckets the
    pixels agree within ±1 LSB (each bucket is its own compiled program).

    The regeneration fast path (PR 4) layers three optimizations on top:

    * ``pixel_format='uint8'`` routes through
      :meth:`VAE.decode_u8` — one compiled graph from normalized latent to
      displayable uint8 bytes (1/4 the device->host transfer and pixel
      cache charge of float32);
    * host DEFLATE decompression is *memoized per oid* (bounded LRU keyed
      on the exact blob), so repeat decodes of a hot object — and every
      coalesced duplicate — never pay the codec twice;
    * ``pipeline=True`` overlaps codec and compute: each chunk's decode
      dispatches asynchronously, the next chunk's latents decompress while
      it runs on device, and the result is only awaited when the following
      dispatch is in flight (no ``block_until_ready`` between chunks).
    """

    def __init__(self, vae: VAE, buckets: Sequence[int] = (1, 2, 4, 8),
                 pixel_format: str = "uint8", pipeline: bool = True,
                 memo_entries: int = 256):
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive: {buckets!r}")
        if pixel_format not in ("uint8", "float32"):
            raise ValueError(f"pixel_format must be uint8|float32: "
                             f"{pixel_format!r}")
        self.vae = vae
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.max_batch = self.buckets[-1]
        self.pixel_format = pixel_format
        self.pipeline = bool(pipeline)
        self.memo_entries = int(memo_entries)
        # oid -> (compressed blob, exec node) in arrival order; the blob
        # decompresses lazily at flush (overlapped with the device decode)
        self._pending: Dict[int, Tuple[bytes, Any]] = {}
        # oid -> (blob, decompressed z): reused only when the blob matches
        self._zmemo: "OrderedDict[int, Tuple[bytes, np.ndarray]]" = \
            OrderedDict()
        # (bucket, latent shape) pairs whose decode is compiled
        self._warm: set = set()
        # (bucket, latent shape) pairs this batcher has decoded, in first-
        # seen order — the kernel autotuner's tune-on-first-miss feed
        self._shape_log: List[Tuple[int, Tuple[int, ...]]] = []
        self._shapes_seen: set = set()
        self.stats = {"decodes": 0, "batches": 0, "coalesced": 0,
                      "padded_slots": 0, "decompressions": 0, "memo_hits": 0}
        self.last_per_image_ms: Dict[int, float] = {}
        #: Cumulative decode wall occupancy (ms) — the engine-side analog
        #: of ``GpuQueue.busy_ms``, window deltas feed the autoscaler.
        self.busy_ms = 0.0

    def __len__(self) -> int:
        return len(self._pending)

    def clear(self) -> None:
        """Drop everything pending (a window aborted mid-admission)."""
        self._pending.clear()

    def forget(self, oid: int) -> None:
        """Invalidate the decompression memo for ``oid`` (its durable blob
        was deleted or rewritten)."""
        self._zmemo.pop(oid, None)

    def submit(self, oid: int, blob: bytes, node: Any) -> bool:
        """Queue a decode for ``oid``; returns True if newly enqueued,
        False if it coalesced with an in-flight decode of the same oid."""
        if oid in self._pending:
            self.stats["coalesced"] += 1
            return False
        self._pending[oid] = (blob, node)
        return True

    def bucket_for(self, n: int) -> int:
        """Smallest configured bucket >= n (n itself beyond the largest)."""
        for b in self.buckets:
            if b >= n:
                return b
        return n

    # -- decode plumbing ------------------------------------------------------

    def _decode_fn(self, zb):
        if self.pixel_format == "uint8":
            return self.vae.decode_u8(zb)
        return self.vae.decode(zb)

    def _dispatch(self, zb, bucket: int):
        """Enqueue one chunk's decode (asynchronous)."""
        with TraceAnnotation("lb.dispatch", bucket=bucket):
            return self._decode_fn(zb)

    @staticmethod
    def _await(fut, bucket: int) -> np.ndarray:
        """Wait for a dispatched decode and copy its pixels to the host."""
        with TraceAnnotation("lb.collect", bucket=bucket) as span:
            imgs = np.asarray(fut)                    # blocks until done
            span.set_metadata(bytes=imgs.nbytes)
        return imgs

    def decode_single(self, z: np.ndarray) -> np.ndarray:
        """One-off decode of a single latent in the configured pixel
        format (prewarm / promotion paths outside the batched window)."""
        zb = self.vae.place(np.asarray(z, np.float32)[None])
        return np.asarray(self._decode_fn(zb))[0]

    def _warm_up(self, bucket: int, latent_hwc) -> float:
        """Compile (bucket, latent shape) on a zeros batch unless it is
        warm; returns the seconds spent (0.0 when already warm)."""
        key = (int(bucket), tuple(int(v) for v in latent_hwc))
        if key in self._warm:
            return 0.0
        with TraceAnnotation("lb.warm_up", bucket=key[0]) as span:
            t0 = time.perf_counter()
            counters = (conv_paths, linattn_calls)
            before = [dict(c) for c in counters]
            z = self.vae.place(np.zeros(key[:1] + key[1], np.float32))
            np.asarray(self._decode_fn(z))
            self._warm.add(key)
            # banded convs this compile traced on each path, and linear-
            # attention kernel calls (all 0 when the decode's trace was
            # already cached)
            span.set_metadata(**{k: c[k] - b[k]
                                 for c, b in zip(counters, before)
                                 for k in c})
            return time.perf_counter() - t0

    def prewarm(self, latent_hwc: Tuple[int, int, int]) -> Dict[int, float]:
        """Compile every bucket's decode for ``latent_hwc`` up front so no
        serving window ever pays jit time (first-flush warmup otherwise
        compiles lazily, bucket by bucket).  With a tuning cache active,
        the trace consults it — so prewarming compiles the *tuned* kernel
        shapes.  Returns bucket -> warm-up seconds (compile plus one
        decode; 0.0 for a bucket that was already warm)."""
        out = {}
        for b in self.buckets:
            self._note_shape(b, latent_hwc)
            out[b] = self._warm_up(b, latent_hwc)
        return out

    def _note_shape(self, bucket: int, latent_hwc) -> None:
        key = (int(bucket), tuple(int(v) for v in latent_hwc))
        if key not in self._shapes_seen:
            self._shapes_seen.add(key)
            self._shape_log.append(key)

    def drain_shapes(self) -> List[Tuple[int, Tuple[int, ...]]]:
        """(bucket, latent shape) pairs first seen since the last drain —
        the engine forwards them to the kernel autotuner."""
        out, self._shape_log = self._shape_log, []
        return out

    def rewarm(self) -> None:
        """Drop compiled decodes so the next warmup re-traces the kernel
        dispatch (picking up freshly tuned block shapes); called by the
        engine after a tuning step so recompiles land in warmup, never in
        a timed serving region."""
        self._warm.clear()
        refresh = getattr(self.vae, "refresh_kernels", None)
        if refresh is not None:
            refresh()

    def _latent_of(self, oid: int, blob: bytes) -> np.ndarray:
        """Memoized host decompression (fixed decode dtype: determinism
        holds per (latent, stack) pair)."""
        hit = self._zmemo.get(oid)
        if hit is not None and hit[0] == blob:
            self._zmemo.move_to_end(oid)
            self.stats["memo_hits"] += 1
            return hit[1]
        self.stats["decompressions"] += 1
        with TraceAnnotation("lb.decompress", oid=oid, bytes=len(blob)):
            z = np.asarray(decompress_latent(blob), np.float32)
        if self.memo_entries > 0:
            self._zmemo[oid] = (blob, z)
            self._zmemo.move_to_end(oid)
            while len(self._zmemo) > self.memo_entries:
                self._zmemo.popitem(last=False)
        return z

    def _assemble(self, chunk):
        """Host half of one chunk: decompress (memoized), pad to the
        bucket, stack, and make sure the bucket's shape is compiled."""
        n_real = len(chunk)
        bucket = self.bucket_for(n_real)
        with TraceAnnotation("lb.assemble", bucket=bucket, n_real=n_real):
            zs = [self._latent_of(oid, blob) for oid, (blob, _) in chunk]
            zs.extend([zs[-1]] * (bucket - n_real))   # pad: last real z
            zb = np.stack(zs)
            self._note_shape(bucket, zb.shape[1:])
            # compile a new (bucket, latent shape) outside the timed region
            # so jit compile time never poisons the tuner's decode EWMA
            self._warm_up(bucket, zb.shape[1:])
            with TraceAnnotation("lb.place", bytes=zb.nbytes):
                return self.vae.place(zb), bucket, n_real

    def _account(self, chunk, imgs, per_image_ms, bucket, n_real):
        self.stats["batches"] += 1
        self.stats["decodes"] += n_real
        self.stats["padded_slots"] += bucket - n_real
        self.busy_ms += per_image_ms * n_real
        out = {}
        for i, (oid, (_, node)) in enumerate(chunk):
            if node.tuner is not None:
                node.tuner.observe_decode_ms(per_image_ms)
            self.last_per_image_ms[oid] = per_image_ms
            out[oid] = imgs[i]
        return out

    def flush(self) -> Dict[int, np.ndarray]:
        """Decode everything pending; returns oid -> image and feeds each
        exec node's tuner the per-image wall clock of its batch.

        With ``pipeline=True`` chunk k+1's host decompression overlaps
        chunk k's in-flight device decode; the await of chunk k happens
        only after chunk k+1 has dispatched."""
        results: Dict[int, np.ndarray] = {}
        items = list(self._pending.items())
        self._pending.clear()
        self.last_per_image_ms = {}
        chunks = [items[s:s + self.max_batch]
                  for s in range(0, len(items), self.max_batch)]
        if not self.pipeline:
            for chunk in chunks:
                zb, bucket, n_real = self._assemble(chunk)
                t0 = time.perf_counter()
                imgs = self._await(self._dispatch(zb, bucket), bucket)
                ms = (time.perf_counter() - t0) * 1e3
                results.update(self._account(chunk, imgs, ms / n_real,
                                             bucket, n_real))
            return results

        inflight = None           # (chunk, future, start, bucket, n_real)
        prev_done = 0.0
        for chunk in chunks:
            zb, bucket, n_real = self._assemble(chunk)
            t0 = time.perf_counter()
            fut = self._dispatch(zb, bucket)
            if inflight is not None:
                prev_done = self._collect(results, *inflight)
            # the device runs chunks serially: this chunk only starts once
            # the previous one finished, so its timed span begins there
            inflight = (chunk, fut, max(t0, prev_done), bucket, n_real)
        if inflight is not None:
            self._collect(results, *inflight)
        return results

    def _collect(self, results, chunk, fut, start, bucket, n_real) -> float:
        imgs = self._await(fut, bucket)
        done = time.perf_counter()
        per_image_ms = (done - start) * 1e3 / n_real
        results.update(self._account(chunk, imgs, per_image_ms, bucket,
                                     n_real))
        return done


@dataclasses.dataclass
class _Ticket:
    """One request's routing decision, held across the batched decode."""
    oid: int
    outcome: str
    owner: _Node
    exec_node: Optional[_Node] = None
    img: Optional[np.ndarray] = None          # set on image hit
    write_image: bool = False                 # promote/pin decision at lookup
    spilled: bool = False
    #: measured durable-fetch wall plus the store's modelled latency draw
    #: (``store.fetch_ms``); the tuner's fetch EWMA reads it
    fetch_ms: float = 0.0
    regen_ms: float = 0.0                     # measured regeneration wall
    decode_ms: float = 0.0                    # per-image share of its batch


class ServingEngine:
    """Single-process stand-in for the Ray fleet: N logical nodes share one
    device, but the cache/routing/tuning logic is the production code —
    and, since the store refactor, the exact same ``TierWalk`` the
    simulator backend classifies with."""

    def __init__(self, vae: VAE, store: LatentStore,
                 cfg=None, image_bytes: float = 16e3,
                 latent_bytes: float = 13e3,
                 recipes: Optional[RegenTierStore] = None):
        """``cfg`` is either a :class:`StoreConfig` (the facade path — its
        ``image_bytes``/``latent_bytes`` fields win) or a legacy
        :class:`EngineConfig` combined with the explicit size arguments."""
        self.vae = vae
        self.store = store
        if isinstance(cfg, StoreConfig):
            self.cfg = cfg
        else:
            self.cfg = (cfg or EngineConfig()).store_config(
                image_bytes, latent_bytes)
        self.recipes = recipes
        self.walk = TierWalk(
            self.cfg,
            durable=DurableTier(store),
            recipes=RecipeTier(recipes) if recipes is not None else None)
        self.nodes = [_Node(i, t) for i, t in enumerate(self.walk.caches)]
        for node in self.nodes:
            # capacity evictions drop the decoded/compressed payload too
            node.tier.evict_cb(node.drop_payloads)
        self.router = self.walk.router
        self.batcher = DecodeBatcher(vae, self.cfg.decode_buckets,
                                     pixel_format=self.cfg.pixel_format)
        self.stats = self.walk.counts           # shared hit/spill accounting
        self._inflight: List[_Ticket] = []      # open microbatch (admit/dispatch)
        self._calls = 0                         # serve_window calls
        # -- quantized decoder (gated) + persistent kernel autotuner ---------
        self.gate_lsb: Optional[Dict[int, int]] = None
        if self.cfg.weight_dtype != "float32":
            if self.cfg.pixel_format != "uint8":
                raise ValueError(
                    "weight_dtype quantization serves the uint8 fast path "
                    "only; the float32 pixel format stays on f32 weights")
            from repro.vae.quantize import check_u8_gate
            vae.set_weight_dtype(self.cfg.weight_dtype)
            # the ±1-LSB open-time gate: quantized vs f32-oracle uint8
            # pixels on probe latents, every decode bucket — raises
            # QuantizationGateError (config rejected) on breach
            self.gate_lsb = check_u8_gate(
                vae, self.cfg.decode_buckets,
                (8, 8, vae.cfg.latent_channels))
        # -- elastic autoscaling (off by default: no controller at all) ------
        # the engine's decode fleet is one shared device, so the GPU knob
        # moves a VIRTUAL fleet width (provisioned-cost accounting + the
        # utilization denominator); the cache knob is fully real via the
        # walk's capacity handoff
        self.gpus_per_node = int(getattr(self.cfg, "gpus_per_node", 1))
        self._opened_s = self.cfg.now_s()
        self._gpu_ms = 0.0
        self._cache_byte_ms = 0.0
        self._acct_mark_s = self._opened_s
        self._cache_bytes_per_node = float(self.cfg.cache_bytes_per_node)
        self.autoscaler = None
        if getattr(self.cfg, "autoscale", False):
            from repro.core.autoscale import (AutoscaleConfig,
                                              AutoscaleController, PlantState)
            from repro.core.cost_model import params_for_store
            acfg = self.cfg.autoscale_cfg or dataclasses.replace(
                AutoscaleConfig(), params=params_for_store(self.cfg))
            self.autoscaler = AutoscaleController(
                PlantState(self.gpus_per_node, len(self.walk.caches),
                           self._cache_bytes_per_node), acfg)
            self._as_mark = {"reqs": 0, "now_s": self._opened_s,
                             "busy": 0.0, "image_hits": 0}
        self.autotuner = None
        self.tuning_cache = None
        if self.cfg.autotune:
            from repro.kernels import autotune as _at
            path = (os.path.join(self.cfg.data_dir, _at.CACHE_FILENAME)
                    if self.cfg.data_dir else None)
            self.tuning_cache = _at.TuningCache.load(path)
            _at.set_active_cache(self.tuning_cache)
            self.autotuner = _at.KernelAutotuner(
                self.tuning_cache, vae.cfg,
                weight_dtype=self.cfg.weight_dtype)

    def prewarm_decode(self, latent_hwc: Tuple[int, int, int]
                       ) -> Dict[int, float]:
        """Compile every decode bucket for the given latent shape up
        front, so no serving batch ever pays jit time; returns bucket ->
        warm-up seconds."""
        return self.batcher.prewarm(latent_hwc)

    # -- writes ---------------------------------------------------------------

    def put(self, oid: int, image: Optional[np.ndarray] = None,
            latent: Optional[np.ndarray] = None,
            recipe: Optional[Recipe] = None) -> int:
        """Durable write: encode (if given pixels) -> compress -> latent
        store; the recipe (if any) becomes the coldest durability class.
        Overwriting an existing object purges its cached copies (pixels,
        latents, memo) so no tier can keep serving the old content.
        Returns the durable byte count."""
        if oid in self.store:           # overwrite: drop every cached copy
            for tier in self.walk.caches:
                tier.evict(oid)
            for node in self.nodes:
                node.drop_payloads(oid)
        if latent is None:
            if image is None:
                if recipe is None:
                    raise ValueError("put needs an image, latent, or recipe")
                image = synthesize_image(recipe)
            img4 = np.asarray(image)
            if img4.dtype == np.uint8:      # display bytes -> [-1, 1] floats
                img4 = img4.astype(np.float32) / 127.5 - 1.0
            img4 = img4.astype(np.float32)
            if img4.ndim == 3:
                img4 = img4[None]
            latent = np.asarray(
                self.vae.encode_mean(img4))[0].astype(np.float16)
        blob = compress_latent(np.asarray(latent))
        self.store.put(oid, blob)
        self.batcher.forget(oid)            # durable blob rewritten
        if recipe is not None and self.recipes is not None:
            self.recipes.put(oid, float(len(blob)), recipe=recipe)
        return len(blob)

    def delete(self, oid: int) -> bool:
        """Remove from every tier, payload dicts included."""
        found = self.walk.delete(oid)
        for node in self.nodes:
            node.drop_payloads(oid)
        self.batcher.forget(oid)
        return found

    def demote(self, oid: int, rung=None) -> bool:
        """Demote down the rate-distortion ladder.  Default (None /
        "recipe"): drop the durable latent, keep the recipe (recipe-only
        class) — cached copies are purged so the next read exercises
        regeneration, and the eviction listeners drop the decoded
        payloads with them.  A lossy rung re-encodes the durable blob at
        that colder quality instead (deferred to compaction on a
        persistent box); cached latents/pixels are left to age out, and
        the batcher memo is keyed on blob bytes so a rewritten blob can
        never serve a stale decode."""
        return self.walk.demote(oid, rung)

    def promote(self, oid: int) -> bool:
        """Regenerate a demoted object's latent back into the durable tier
        without waiting for a read to pay the regen latency."""
        if self.recipes is None or not self.recipes.is_demoted(oid):
            return False
        self._regenerate(oid)
        return True

    def prewarm(self, oid: int) -> bool:
        """Decode now and pin pixels at the hash owner (no stats impact)."""
        blob = self.store.get(oid)
        if blob is None:
            return False
        z = np.asarray(decompress_latent(blob), np.float32)
        img = self.batcher.decode_single(z)
        owner = self.nodes[self.walk._idx[self.walk.router.ring.owner(oid)]]
        owner.cache.insert_image(oid, nbytes=img.nbytes)
        owner.images[oid] = img
        return True

    def _regenerate(self, oid: int) -> bytes:
        """Recipe -> pixels -> latent -> durable re-admission (bit-exact on
        the same stack, which is what makes recipes a durability class)."""
        recipe = self.recipes.recipe_of(oid) if self.recipes else None
        if recipe is None:
            raise KeyError(f"object {oid} has no recipe to regenerate from")
        z = np.asarray(self.vae.encode_mean(
            synthesize_image(recipe)))[0].astype(np.float16)
        blob = compress_latent(z)
        self.store.put(oid, blob)
        self.batcher.forget(oid)            # durable blob rewritten
        self.recipes.readmit(oid, float(len(blob)), now_mo=0.0)
        return blob

    # -- request admission ---------------------------------------------------

    def _lookup(self, oid: int) -> _Ticket:
        """Route one request up to (but excluding) the decode: the shared
        tier-walk classifies and admits; :meth:`_route` materializes
        payloads (durable fetch / regeneration) and enqueues the decode.
        The span records the hit class and whether the pixels were already
        in hand (``ready``)."""
        with TraceAnnotation("lb.lookup", call=self._calls, oid=oid) as span:
            t = self._route(oid)
            span.set_metadata(cls=t.outcome, ready=int(t.img is not None))
        return t

    def _route(self, oid: int) -> _Ticket:
        ticket = self.walk.lookup(
            oid, depth_of=lambda i: self.nodes[i].queue_depth)
        owner = self.nodes[ticket.owner]
        exec_node = self.nodes[ticket.exec_node]

        if ticket.hit_class == IMAGE_HIT:
            img = owner.images.get(oid)
            if img is not None:
                return _Ticket(oid, IMAGE_HIT, owner, img=img)
            # admitted to the image tier, but the pixel payload is still
            # in-flight in the open microbatch: join the pending decode
            # (single-flight) and write back on dispatch.
            blob = owner.latents.get(oid) or self.store.get(oid)
            if blob is None:
                raise KeyError(f"object {oid} not in store")
            if self.batcher.submit(oid, blob, owner):
                owner.queue_depth += 1
            return _Ticket(oid, IMAGE_HIT, owner, exec_node=owner,
                           write_image=True)

        fetch_ms = regen_ms = 0.0
        if ticket.hit_class == LATENT_HIT:
            blob = owner.latents.get(oid) or self.store.get(oid)
            if blob is None:
                raise KeyError(f"object {oid} lost its latent payload")
        elif ticket.needs_regen:
            t0 = time.perf_counter()
            blob = self._regenerate(oid)
            regen_ms = (time.perf_counter() - t0) * 1e3
            # regen replaces the durable fetch on the miss path, so it
            # feeds the fetch EWMA (same signal class on both backends)
            if owner.tuner is not None:
                owner.tuner.observe_fetch_ms(regen_ms)
            if self.walk.admit_latent(ticket.owner, oid):
                owner.latents[oid] = blob
        else:                                         # durable fetch
            t0 = time.perf_counter()
            with TraceAnnotation("lb.fetch", oid=oid) as span:
                blob = self.store.get(oid)
                span.set_metadata(bytes=len(blob or b""))
            if blob is None:
                raise KeyError(f"object {oid} has no durable payload "
                               "(size-only registration?)")
            # store warmth keys on the INJECTABLE clock (cfg.now_s), not
            # bare wall time, so latency draws are deterministic under test
            fetch_ms = ((time.perf_counter() - t0) * 1e3
                        + self.store.fetch_ms(oid, self.cfg.now_s()))
            if owner.tuner is not None:
                owner.tuner.observe_fetch_ms(fetch_ms)
            if self.walk.admit_latent(ticket.owner, oid):
                owner.latents[oid] = blob

        if self.batcher.submit(oid, blob, exec_node):
            exec_node.queue_depth += 1          # one slot per unique decode
        return _Ticket(oid, ticket.hit_class, owner, exec_node=exec_node,
                       write_image=ticket.write_image, spilled=ticket.spilled,
                       fetch_ms=fetch_ms, regen_ms=regen_ms)

    # -- public API ----------------------------------------------------------

    def get(self, oid: int) -> Tuple[np.ndarray, str]:
        return self.get_many([oid])[0]

    def get_many(self, oids: Sequence[int]
                 ) -> List[Tuple[np.ndarray, str]]:
        """Serve one group of requests with one batched decode flush;
        returns ``(pixels, hit_class)`` pairs in request order."""
        return [(t.img, t.outcome) for t in self.serve_window(oids)]

    def admit(self, oid: int) -> _Ticket:
        """Admit one request into the currently *open* microbatch without
        flushing it: classify via the shared walk, materialize payloads
        (durable fetch / regeneration), and enqueue the decode.  This is
        the continuous feed path of the serving runtime — the scheduler
        decides when the batch closes (size bucket filled or deadline
        slack exhausted) and then calls :meth:`dispatch`.  The returned
        ticket is live: its ``img``/``decode_ms`` fill in at dispatch.
        """
        try:
            ticket = self._lookup(int(oid))
        except Exception:
            self._abort_open_batch()
            raise
        self._inflight.append(ticket)
        return ticket

    def dispatch(self) -> List[_Ticket]:
        """Close the open microbatch: flush the queued decodes, write
        decoded pixels back to their hash owners (cache pinning) in
        admission order, then run the bounded end-of-batch durable
        maintenance.  Returns the admitted tickets in admission order."""
        tickets, self._inflight = self._inflight, []
        decoded = self._flush()
        with TraceAnnotation("lb.writeback", call=self._calls):
            touched = {}
            for t in tickets:
                if t.img is not None:
                    continue
                img = decoded[t.oid]
                t.decode_ms = self.batcher.last_per_image_ms.get(t.oid, 0.0)
                # cache pinning: decoded result written back to the OWNER
                if t.write_image or t.owner.cache.contains(t.oid) == "image":
                    t.owner.images[t.oid] = img
                    # charge the pixel tier the stored array's real bytes
                    # (uint8 on the fast path) — a size-only correction, so
                    # the LRU order stays identical to the simulator's
                    t.owner.cache.set_image_nbytes(t.oid, img.nbytes)
                touched[id(t.owner)] = t.owner
                t.img = img
            for node in touched.values():
                self._gc(node)
            self._durable_maintenance()
        return tickets

    def _abort_open_batch(self) -> None:
        """A group aborted mid-admission (e.g. unknown oid) must not leak
        queued decodes, queue-depth, or half-admitted tickets into the
        next group."""
        self.batcher.clear()
        for n in self.nodes:
            n.queue_depth = 0
        self._inflight = []

    def serve_window(self, oids: Sequence[int]) -> List[_Ticket]:
        """Serve one fixed group of requests with a single batched decode
        flush — ``admit`` every id in request order (cache state evolves
        exactly as with sequential ``get`` calls), then ``dispatch``.
        Tickets carry the measured per-request latency components for
        ``GetResult``.  The serving runtime's drain-mode conformance
        guarantee is defined against this path.
        """
        self._calls += 1
        with TraceAnnotation("lb.serve_window", call=self._calls,
                             n=len(oids)):
            for oid in oids:
                self.admit(oid)
            return self.dispatch()

    def serve_stream(self, requests, runtime_cfg=None):
        """Replay an open-loop request stream through the event-loop
        serving runtime (simulated clock, per-tenant QoS, SLO-aware
        admission), feeding this engine's batcher continuously via
        :meth:`admit`/:meth:`dispatch`.  ``requests`` is a sequence of
        :class:`repro.serve.runtime.Request` or a ``SyntheticTrace``;
        returns a :class:`repro.serve.runtime.StreamReport`."""
        from repro.serve.runtime import RuntimeConfig, ServingRuntime
        if runtime_cfg is None:
            runtime_cfg = RuntimeConfig.from_store(self.cfg)
        return ServingRuntime.for_engine(self, runtime_cfg).run(requests)

    def _durable_maintenance(self) -> None:
        """End-of-batch durability work, threaded into the request loop:
        flush write-behind appends (acknowledging them), run at most one
        online-compaction step, and — with autotuning on — tune at most
        one missing kernel-shape key (tune-on-first-miss).  Bounded work
        per dispatched batch, so serving latency never absorbs a
        stop-the-world sweep; the first two are no-ops on the in-memory
        backend."""
        self.store.flush()
        self.store.maybe_compact()
        if self.autoscaler is not None:
            self._autoscale_step()
        if self.autotuner is not None:
            for bucket, hwc in self.batcher.drain_shapes():
                self.autotuner.note_bucket(bucket, hwc)
            if self.autotuner.step(1):
                # new winners: recompile in warmup, not in a timed region
                self.batcher.rewarm()

    # -- elastic autoscaling --------------------------------------------------
    def _account_provisioned(self) -> None:
        """Advance the provisioned GPU/cache time integrals to the
        (injectable) wall clock — held capacity, busy or idle."""
        now_s = self.cfg.now_s()
        dt_ms = (now_s - self._acct_mark_s) * 1e3
        if dt_ms <= 0.0:
            return
        self._gpu_ms += dt_ms * len(self.nodes) * self.gpus_per_node
        self._cache_byte_ms += (dt_ms * len(self.nodes)
                                * self._cache_bytes_per_node)
        self._acct_mark_s = now_s

    def _autoscale_step(self) -> None:
        """Engine-side control step, run inside the bounded end-of-batch
        maintenance slice.  Observations come from the engine's own
        signals: walk hit counts (arrival volume + decode fraction) and
        the batcher's measured decode occupancy.  The engine has no plant
        queue, so it scales on utilization alone (queue_p99 = 0)."""
        from repro.core.autoscale import WindowObs
        from repro.store.api import HIT_CLASSES
        mark = self._as_mark
        reqs = sum(self.walk.counts[k] for k in HIT_CLASSES)
        if reqs - mark["reqs"] < self.autoscaler.cfg.window:
            return
        now_s = self.cfg.now_s()
        span_ms = (now_s - mark["now_s"]) * 1e3
        n = reqs - mark["reqs"]
        hits = self.walk.counts[IMAGE_HIT] - mark["image_hits"]
        obs = WindowObs(
            requests=n, span_ms=span_ms,
            busy_ms=max(0.0, self.batcher.busy_ms - mark["busy"]),
            decode_frac=1.0 - hits / n if n else 1.0)
        self._as_mark = {"reqs": reqs, "now_s": now_s,
                         "busy": self.batcher.busy_ms,
                         "image_hits": self.walk.counts[IMAGE_HIT]}
        ev = self.autoscaler.step(obs)
        if ev is not None:
            self._apply_scale(ev.state)

    def _apply_scale(self, state) -> None:
        self._account_provisioned()
        self.gpus_per_node = int(state.gpus_per_node)
        if state.cache_bytes_per_node != self._cache_bytes_per_node:
            self._cache_bytes_per_node = float(state.cache_bytes_per_node)
            self.walk.set_cache_capacity(self._cache_bytes_per_node)

    def _flush(self) -> Dict[int, np.ndarray]:
        n = len(self.batcher)
        with TraceAnnotation("lb.flush", call=self._calls, decodes=n,
                             chunks=-(-n // self.batcher.max_batch)):
            try:
                return self.batcher.flush()
            finally:
                for node in self.nodes:
                    node.queue_depth = 0        # all in-flight decodes drained

    def _gc(self, node: _Node) -> None:
        if len(node.images) > 2 * len(node.cache.image_tier) + 32:
            live = set(iter(node.cache.image_tier))
            node.images = {k: v for k, v in node.images.items() if k in live}
        if len(node.latents) > 2 * len(node.cache.latent_tier) + 32:
            live = set(iter(node.cache.latent_tier))
            node.latents = {k: v for k, v in node.latents.items()
                            if k in live}

    def summary(self) -> Dict[str, Any]:
        out = self.walk.summary()
        # decode-fleet observability, mirroring the simulator backend's keys
        self._account_provisioned()
        out["gpu_seconds"] = self.batcher.busy_ms / 1e3
        out["decode_gpus"] = len(self.nodes) * self.gpus_per_node
        out["decode_util"] = (min(1.0, self.batcher.busy_ms / self._gpu_ms)
                              if self._gpu_ms > 0 else 0.0)
        out["provisioned_gpu_ms"] = self._gpu_ms
        out["provisioned_cache_byte_ms"] = self._cache_byte_ms
        if self.autoscaler is not None:
            out.update(self.autoscaler.summary())
        out["decode_batches"] = self.batcher.stats["batches"]
        out["decodes"] = self.batcher.stats["decodes"]
        out["coalesced_decodes"] = self.batcher.stats["coalesced"]
        out["decompressions"] = self.batcher.stats["decompressions"]
        out["decompress_memo_hits"] = self.batcher.stats["memo_hits"]
        out["pixel_format"] = self.cfg.pixel_format
        out["weight_dtype"] = self.cfg.weight_dtype
        if self.gate_lsb is not None:
            out["quantize_gate_lsb"] = dict(self.gate_lsb)
        if self.tuning_cache is not None:
            out["tuned_kernel_keys"] = len(self.tuning_cache)
            out["tuning_pending"] = self.autotuner.pending
        return out
