"""Serving launcher — the paper's end-to-end path on real compute, through
the ``LatentBox`` object-store facade.

Builds a corpus of generated images, ``put``s them (encode -> compress ->
durable latent write), then replays a trace slice with windowed
``get_many`` — consistent-hash routing, dual-format caching, adaptive
tuning, and microbatched jitted decodes all behind the one facade.  The
decoder is chosen by name from :data:`repro.vae.model.CONFIGS` (``demo``
or the paper's ``sd35_vae`` at 1024²), with seeded random weights
calibrated into the display range.

    PYTHONPATH=src python -m repro.launch.serve --requests 800 --objects 60
    PYTHONPATH=src python -m repro.launch.serve --decoder sd35_vae \\
        --objects 8 --requests 16 --buckets 1 2

:func:`put_corpus` and :func:`serve_windows` are the launcher's
put-then-serve body; ``chip_smoke.py`` drives the same two functions.
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.regen_tier import Recipe
from repro.core.tuner import TunerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.store import LatentBox, StoreConfig
from repro.store.api import GetResult, PutResult
from repro.trace.synth import TraceConfig, generate_trace
from repro.vae.model import CONFIGS, calibrated_vae


def device_summary() -> str:
    """``platform=... device_kind=... devices=N`` as JAX reports them."""
    devs = jax.devices()
    return (f"platform={devs[0].platform} "
            f"device_kind={devs[0].device_kind} devices={len(devs)}")


def put_corpus(box: LatentBox, oids: Sequence[int], res: int,
               model: str = "demo") -> List[PutResult]:
    """``put`` one generated res x res image per oid, by recipe: the
    recipe is synthesized, encoded, compressed and durably written, and
    kept as the object's coldest durability class."""
    return [box.put(int(oid), recipe=Recipe(seed=int(oid), height=res,
                                            width=res, model=model))
            for oid in oids]


def serve_windows(box: LatentBox, ids: Sequence[int], window: int
                  ) -> Tuple[List[GetResult], float]:
    """Serve ``ids`` as consecutive ``get_many`` windows of ``window``
    requests; returns the results in request order and the wall seconds."""
    out: List[GetResult] = []
    window = max(1, int(window))
    t0 = time.perf_counter()
    for start in range(0, len(ids), window):
        out += box.get_many([int(oid) for oid in ids[start:start + window]])
    return out, time.perf_counter() - t0


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--decoder", choices=sorted(CONFIGS), default="demo")
    ap.add_argument("--objects", type=int, default=60)
    ap.add_argument("--requests", type=int, default=800)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--res", type=int, default=None,
                    help="image side (default: 32 for demo, 1024 otherwise)")
    ap.add_argument("--batch", type=int, default=8,
                    help="request window size fed to the microbatching "
                         "decode scheduler (1 = sequential gets)")
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    help="decode batch buckets (default: StoreConfig's)")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    res = args.res or (32 if args.decoder == "demo" else 1024)
    print(f"[serve] {device_summary()} compile_cache={cache_dir}")
    vae = calibrated_vae(args.decoder, seed=0)
    img_bytes = res * res * 3
    cfg = StoreConfig(
        n_nodes=args.nodes,
        cache_bytes_per_node=args.objects * img_bytes * 0.15,
        image_bytes=float(img_bytes), latent_bytes=float(img_bytes) / 5,
        tuner=TunerConfig(window=100, step=0.02))
    if args.buckets:
        cfg.decode_buckets = tuple(args.buckets)
    box = LatentBox.engine(vae=vae, config=cfg)

    print(f"[serve] putting {args.objects} generated {res}x{res} images "
          f"-> latents ({args.decoder} decoder)")
    puts = put_corpus(box, range(args.objects), res, model=args.decoder)
    print(f"[serve] mean compressed latent "
          f"{np.mean([p.stored_bytes for p in puts]):.0f} B "
          f"vs raw pixels {img_bytes} B")

    tr = generate_trace(TraceConfig(n_objects=args.objects,
                                    n_requests=args.requests * 2,
                                    span_days=5, seed=3))
    ids = tr.object_ids[:args.requests]
    _, dt = serve_windows(box, ids, args.batch)
    s = box.summary()
    print(f"[serve] {len(ids)} requests in {dt:.1f}s "
          f"({1e3 * dt / len(ids):.1f} ms/req wall, window={args.batch}, "
          f"{device_summary()})")
    print(f"[serve] image-hit {s['image_hit_frac']:.1%}, "
          f"decode fraction {s['decode_frac']:.1%}, "
          f"spilled {s['spilled']}, alpha per node {s['alpha']}")
    batches = max(1, s['decode_batches'])
    print(f"[serve] {s['decodes']} decodes in {s['decode_batches']} batches "
          f"(mean batch {s['decodes'] / batches:.1f}, "
          f"{s['coalesced_decodes']} coalesced in-flight)")


if __name__ == "__main__":
    main()
