"""``LatentBox`` — the single client-facing facade of the object store.

The paper's system is *storage*: objects are put once, read billions of
times, and demoted across durability classes as they cool.  This class is
that contract as an API:

    box = LatentBox.engine()                      # real jitted decode
    box.put(42, image=img, recipe=Recipe(seed=7, height=64, width=64))
    r = box.get(42)                               # GetResult: pixels +
    #                                               hit class + latency
    box.demote(42)                                # recipe-only durability
    box.get(42).regenerated                       # True: cold regen path
    box.stat(42), box.delete(42), box.summary()

``LatentBox.simulated()`` swaps the backend for the discrete latency plant
— same tier walk, same classifications, no GPU — which is how trace-scale
capacity studies and unit tests drive the identical read path the real
engine serves with.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.core.regen_tier import Recipe
from repro.store.api import GetResult, ObjectStat, PutResult, StoreConfig


class LatentBox:
    """Unified object-store facade over a pluggable tier backend."""

    def __init__(self, backend):
        self._backend = backend
        self._meta: Dict[int, Dict[str, Any]] = {}

    # -- constructors --------------------------------------------------------
    @classmethod
    def engine(cls, vae=None, config: Optional[StoreConfig] = None,
               seed: int = 0, shards: int = 1,
               replication: Optional[int] = None, hedge=None,
               fault_plan=None) -> "LatentBox":
        """Real-decode box.  Without an explicit ``vae`` a small demo VAE
        is built (the paper-scale decoder swaps in transparently).
        ``shards > 1`` serves a consistent-hash-sharded cluster of engine
        backends with ``config.n_nodes`` nodes per shard;
        ``replication``/``hedge``/``fault_plan`` configure R-way replica
        placement, hedged reads, and scripted failure injection on that
        cluster (see :class:`~repro.store.sharding.ShardedLatentBox`)."""
        from repro.store.backends import EngineBackend
        if vae is None:
            from repro.vae.model import calibrated_vae
            vae = calibrated_vae("demo", seed=seed)
        if shards > 1 or (replication or 1) > 1 or fault_plan is not None:
            from repro.store.sharding import ShardedLatentBox
            return cls(ShardedLatentBox.engine(
                vae, shards, config, replication=replication, hedge=hedge,
                fault_plan=fault_plan))
        return cls(EngineBackend(vae, config))

    @classmethod
    def simulated(cls, config: Optional[StoreConfig] = None,
                  shards: int = 1, replication: Optional[int] = None,
                  hedge=None, fault_plan=None) -> "LatentBox":
        """Latency-plant box: identical classifications, modeled latency.
        ``shards > 1`` serves a consistent-hash-sharded cluster of sim
        backends, each with its own GPU plant and tuner state;
        ``replication``/``hedge``/``fault_plan`` as for :meth:`engine`."""
        from repro.store.backends import SimBackend
        if shards > 1 or (replication or 1) > 1 or fault_plan is not None:
            from repro.store.sharding import ShardedLatentBox
            return cls(ShardedLatentBox.simulated(
                shards, config, replication=replication, hedge=hedge,
                fault_plan=fault_plan))
        return cls(SimBackend(config))

    @classmethod
    def open(cls, path, mode: str = "engine",
             config: Optional[StoreConfig] = None, vae=None, seed: int = 0,
             shards: int = 1, replication: Optional[int] = None,
             hedge=None, fault_plan=None) -> "LatentBox":
        """Open (or create) a *persistent* box on ``path``.

        The durable-latent and recipe tiers write through one
        log-structured segment store under ``path`` (per-shard
        subdirectories when ``shards > 1``).  The reopen guarantee:
        after ANY process exit — clean ``close()``, hard kill mid-write,
        or kill mid-compaction — ``LatentBox.open(path)`` recovers every
        *acknowledged* put (``PutResult.durable`` / past ``flush()``) and
        serves it bit-exact: same blob bytes, same decoded pixels on the
        same stack, same recipes and demotion flags.  Unacknowledged tail
        records are detected by checksum and cleanly ignored.  Cache
        warmth and store-latency warmth are process state and restart
        cold, like a node rejoining a fleet.
        """
        import dataclasses as _dc
        cfg = _dc.replace(config or StoreConfig(), data_dir=str(path))
        if mode == "engine":
            return cls.engine(vae=vae, config=cfg, seed=seed, shards=shards,
                              replication=replication, hedge=hedge,
                              fault_plan=fault_plan)
        if mode == "sim":
            return cls.simulated(cfg, shards=shards,
                                 replication=replication, hedge=hedge,
                                 fault_plan=fault_plan)
        raise ValueError(f"mode must be 'engine' or 'sim': {mode!r}")

    @property
    def backend(self):
        return self._backend

    # -- writes --------------------------------------------------------------
    def put(self, oid: int, image: Optional[np.ndarray] = None,
            latent: Optional[np.ndarray] = None,
            recipe: Optional[Recipe] = None,
            nbytes: Optional[float] = None,
            meta: Optional[Dict[str, Any]] = None,
            prewarm: bool = False) -> PutResult:
        """Durable write: encode (pixels) -> compress -> latent store.

        Any one of ``image`` / ``latent`` / ``recipe`` suffices on the
        engine backend (a lone recipe is synthesized first); the simulator
        additionally accepts ``nbytes``-only registrations.  ``prewarm``
        pins decoded pixels at the hash owner so the first read is an
        image hit.
        """
        res = self._backend.put(int(oid), image=image, latent=latent,
                                recipe=recipe, nbytes=nbytes, prewarm=prewarm)
        if meta is not None:
            self._meta[int(oid)] = dict(meta)
        return res

    # -- reads ---------------------------------------------------------------
    def get(self, oid: int) -> GetResult:
        return self.get_many([oid])[0]

    def get_many(self, oids: Sequence[int],
                 timestamps_ms: Optional[Sequence[float]] = None
                 ) -> List[GetResult]:
        """Serve a request window through the tier walk.  ``timestamps_ms``
        drives open-loop trace replay on the simulator backend; the engine
        serves at wall-clock and ignores it."""
        return self._backend.get_many(oids, timestamps_ms=timestamps_ms)

    def serve_stream(self, requests, runtime_cfg=None):
        """Replay an open-loop request stream (timestamped arrivals)
        through the event-loop serving runtime: continuous microbatching,
        per-tenant QoS, SLO classes, and admission control.

        ``requests`` is a :class:`~repro.trace.synth.SyntheticTrace` or a
        sequence of :class:`repro.serve.runtime.Request`; ``runtime_cfg``
        a :class:`repro.serve.runtime.RuntimeConfig` (defaults derive the
        service model from this box's ``StoreConfig``).  Returns a
        :class:`repro.serve.runtime.StreamReport` with per-request
        outcomes in arrival order, the columnar :class:`RequestLog`
        (queue delay, deadlines, tenants), and scheduler counters.
        """
        stream = getattr(self._backend, "serve_stream", None)
        if stream is not None:          # backend owns the continuous feed
            return stream(requests, runtime_cfg=runtime_cfg)
        from repro.serve.runtime import RuntimeConfig, ServingRuntime
        if runtime_cfg is None:
            cfg = getattr(self._backend, "cfg", None)
            runtime_cfg = (RuntimeConfig.from_store(cfg)
                           if cfg is not None else RuntimeConfig())
        return ServingRuntime.for_target(self._backend, runtime_cfg).run(
            requests)

    def pixels_resident(self, oid: int) -> bool:
        """Pure peek: is ``oid`` currently pixel-cache resident at its
        hash owner?  (No stats impact — used by degrade-mode admission.)"""
        probe = getattr(self._backend, "pixels_resident", None)
        return bool(probe(int(oid))) if probe is not None else False

    # -- lifecycle -----------------------------------------------------------
    def delete(self, oid: int) -> bool:
        """Remove the object from every tier (pixels, latents, durable,
        recipe) and forget its metadata.  The metadata is dropped only
        after the backend delete returns: a raising backend must not
        silently lose the object's metadata."""
        found = self._backend.delete(int(oid))
        self._meta.pop(int(oid), None)
        return found

    def stat(self, oid: int) -> Optional[ObjectStat]:
        st = self._backend.stat(int(oid))
        if st is not None:
            st.meta = self._meta.get(int(oid))
        return st

    def demote(self, oid: int, rung=None) -> bool:
        """Demote the object down the rate-distortion ladder.

        Default (``rung=None`` / ``"recipe"``): the pre-ladder behavior —
        drop the durable latent entirely, keep only the recipe; the next
        cold read regenerates (and re-admits) it.  A lossy rung (index
        1-3 or name ``"high"``/``"mid"``/``"low"``) instead re-encodes
        the durable latent at that colder quality: the object keeps its
        durable class, just cheaper bytes (on a persistent box the
        transcode piggybacks on the next compaction pass)."""
        return self._backend.demote(int(oid), rung)

    def promote(self, oid: int) -> bool:
        """Undo a demotion ahead of traffic: regenerate the latent into
        the durable tier now, off the read path."""
        return self._backend.promote(int(oid))

    # -- durability ----------------------------------------------------------
    def flush(self) -> None:
        """Crash-durability barrier: every write accepted so far (including
        write-behind puts) is on disk, and the manifest checkpoint bounds
        the next reopen's recovery scan.  No-op on in-memory boxes."""
        flush = getattr(self._backend, "flush", None)
        if flush is not None:
            flush()

    def close(self) -> None:
        """Seal the active segment, checkpoint the manifest, and release
        file handles.  The box must not be used afterwards; reopen with
        :meth:`open`.  No-op on in-memory boxes."""
        close = getattr(self._backend, "close", None)
        if close is not None:
            close()

    def __enter__(self) -> "LatentBox":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- introspection -------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        return self._backend.summary()

    def __contains__(self, oid: int) -> bool:
        return self._backend.stat(int(oid)) is not None
