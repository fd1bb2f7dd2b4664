"""Chip smoke test: the paper's SD3.5 VAE decode served through the
``LatentBox`` facade on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four-shard box, one shard per chip

One chip: opens a persistent box on the SD3.5 decoder (seeded random
weights, output range calibrated) at 1024², ``put``s a dozen objects by
recipe (encoder, codec, segment log), demotes a few to recipe-only, and
serves request windows through ``get_many`` plus a short ``serve_stream``
— the launcher's own put-then-serve code (``repro.launch.serve``).  Every
hit class must occur, no compile may happen inside the serving window,
every decode kernel must resolve to its Pallas implementation, and every
served image must be within ±1 LSB of an fp32 reference decode of the
same stored latent (``impl="xla"`` under
``default_matmul_precision("highest")``).

``--chips 4``: the same trace on a four-shard box whose shards each
decode on their own chip (weights and latent batches committed there),
compared request by request with the one-shard box: identical hit
classes, pixels within ±1 LSB.

The last line of stdout is one JSON object ``{"ok": true, "device":
{"platform", "kind", "count"}}``.  Without a TPU the script exits
non-zero and prints no result; any failed phase raises.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Decode buckets served.  The largest set whose whole ``decode_u8`` of
#: SD3.5 at 1024² fits one v5e (16 GB HBM): the chip's compiler reports
#: 4.02 GiB of temporaries at bucket 1 and 8.03 GiB at bucket 2, and 16.1
#: GiB at bucket 4.
BUCKETS = (1, 2)
N_OBJECTS = 12
DEMOTED = (9, 10, 11)
#: get_many windows: first reads (full_miss), re-reads (latent_hit, then
#: image_hit with promote_threshold=1) and demoted objects (regen_miss).
WINDOWS = [[0, 1, 2, 3], [0, 1, 4, 9], [0, 1, 5, 10], [2, 3, 6, 7]]
STREAM = [4, 5, 11, 0, 8, 8]
WINDOW = 4
#: Kernel op -> the Pallas kernel its lowering must contain.
KERNELS = {"conv3x3": "_conv_kernel", "gn_silu_conv3x3": "_conv_kernel",
           "upsample_conv3x3": "_upsample_conv_kernel",
           "output_epilogue": "_conv_kernel",
           "flash_attention": "_fa_kernel",
           "group_norm_silu": "_apply_kernel"}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Counts executables built (fresh compiles and persistent-cache
    loads alike) through JAX's backend-compile monitoring event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_event)


def store_config(res: int, buckets: Sequence[int], n_nodes: int = 2):
    """Caches of 16 images per node; the marginal-hit tuner's window never
    fires, so classification depends only on each node's request
    subsequence (the sharded conformance property)."""
    from repro.core.tuner import TunerConfig
    from repro.store import StoreConfig
    image_bytes = float(res * res * 3)
    return StoreConfig(n_nodes=n_nodes, cache_bytes_per_node=16 * image_bytes,
                       image_bytes=image_bytes, latent_bytes=image_bytes / 6,
                       promote_threshold=1, decode_buckets=tuple(buckets),
                       tuner=TunerConfig(window=10 ** 9))


def max_lsb(a: np.ndarray, b: np.ndarray) -> int:
    return int(np.max(np.abs(a.astype(np.int16) - b.astype(np.int16))))


def check_kernel_impls(vae, latent_hwc, res: int) -> Dict[str, str]:
    """Resolve every decode kernel's impl and find its Pallas kernel in
    the lowered decode and encode programs (no compile)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    impl = ops.platform_impl()
    z = jnp.zeros((1,) + tuple(latent_hwc), jnp.float32)
    x = jnp.zeros((1, res, res, vae.cfg.image_channels), jnp.float32)
    text = (vae._decode_u8.lower(vae.decoder, z).as_text()
            + vae._encode.lower(vae.encoder, x).as_text())
    found = {}
    for op, kernel in KERNELS.items():
        n = text.count(f'kernel_name = "{kernel}"')
        found[op] = f"{impl} ({kernel} x{n})"
        if jax.default_backend() == "tpu" and (impl != "pallas" or n == 0):
            raise RuntimeError(f"{op} does not run as a Pallas kernel: "
                               f"impl={impl}, {kernel} x{n}")
    return found


def reference_decoder(vae):
    """The fp32 oracle: the XLA decode under the highest matmul
    precision, one latent at a time."""
    import jax
    from repro.vae.model import decode_u8
    cfg = vae.cfg
    ref = jax.jit(lambda p, z: decode_u8(p, z, cfg, "xla"))

    def decode(z: np.ndarray) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref(vae.decoder, vae.place(
                np.asarray(z, np.float32)[None])))[0]
    return decode


def time_buckets(vae, latent_hwc, buckets, reps: int = 3) -> Dict[int, float]:
    """Per-bucket decode ms (best of ``reps``, ``block_until_ready``)."""
    out = {}
    rng = np.random.default_rng(0)
    for b in buckets:
        best = float("inf")
        for _ in range(reps):
            z = vae.place(rng.standard_normal((b,) + tuple(latent_hwc))
                          .astype(np.float32))
            t0 = time.perf_counter()
            vae.decode_u8(z).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        out[b] = best * 1e3
    return out


def one_chip(workdir: str, decoder: str = "sd35_vae", res: int = 1024,
             buckets: Sequence[int] = BUCKETS, seed: int = 0) -> Dict:
    """The served read path end to end on one device; raises on any
    failed check.  Returns what it measured."""
    counter = CompileCounter()
    try:
        return _one_chip(workdir, decoder, res, buckets, seed, counter)
    finally:
        counter.close()


def _one_chip(workdir, decoder, res, buckets, seed, counter) -> Dict:
    import jax
    from repro.compression.latentcodec import decompress_latent
    from repro.launch.serve import put_corpus, serve_windows
    from repro.serve.runtime import Request, RuntimeConfig
    from repro.store import LatentBox
    from repro.store.api import HIT_CLASSES
    from repro.vae.model import calibrated_vae

    dev = jax.devices()[0]
    out: Dict = {"setup_s": {}}

    t0 = time.perf_counter()
    vae = calibrated_vae(decoder, seed=seed)
    out["setup_s"]["build_and_calibrate"] = time.perf_counter() - t0
    latent_hwc = vae.cfg.latent_shape(res)
    log(f"decoder {decoder}: {vae.decoder_params / 1e6:.1f} M decoder "
        f"params, {res}x{res} images, latents {latent_hwc}")
    impls = check_kernel_impls(vae, latent_hwc, res)
    for op, how in impls.items():
        log(f"kernel {op}: {how}")

    box = LatentBox.open(os.path.join(workdir, "box"), mode="engine",
                         config=store_config(res, buckets), vae=vae)
    engine = box.backend.engine
    t0 = time.perf_counter()
    puts = put_corpus(box, [0], res, model=decoder)   # compiles the encoder
    out["setup_s"]["first_put"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    puts += put_corpus(box, range(1, N_OBJECTS), res, model=decoder)
    out["put_ms_per_object"] = ((time.perf_counter() - t0) * 1e3
                                / (N_OBJECTS - 1))
    if not all(p.durable for p in puts):
        raise RuntimeError("a put was not acknowledged durable")
    log(f"put {N_OBJECTS} objects by recipe: mean "
        f"{np.mean([p.stored_bytes for p in puts]):.0f} B per latent "
        f"(raw pixels {res * res * 3} B)")
    for oid in DEMOTED:
        if not box.demote(oid):
            raise RuntimeError(f"demote({oid}) failed")

    warm = engine.prewarm_decode(latent_hwc)
    for b, s in warm.items():
        log(f"set-up: bucket {b} compile + first decode {s:.1f} s")
    out["setup_s"]["prewarm"] = warm

    # -- the serving window: no compile may happen in here ----------------
    sizes = (vae._decode_u8._cache_size(), vae._encode._cache_size())
    before = counter.count
    t0 = time.perf_counter()
    results, _ = serve_windows(box, [i for w in WINDOWS for i in w], WINDOW)
    reqs = [Request(oid=o, arrival_ms=10.0 * k) for k, o in enumerate(STREAM)]
    report = box.serve_stream(reqs, RuntimeConfig.conformance(
        buckets=tuple(buckets), keep_payloads=True))
    out["window_s"] = time.perf_counter() - t0
    out["window_compiles"] = counter.count - before
    grown = (vae._decode_u8._cache_size(), vae._encode._cache_size()) != sizes
    log(f"serving window: {len(results) + len(reqs)} requests in "
        f"{out['window_s']:.2f} s, compiles inside it: "
        f"{out['window_compiles']}")
    if out["window_compiles"] or grown:
        raise RuntimeError("compiles happened inside the serving window")

    served = [(r.oid, r.hit_class, r.payload) for r in results]
    served += [(reqs[seq].oid, report.outcomes[seq][0], img)
               for seq, img in sorted(report.payloads.items())]
    counts = {c: sum(h == c for _, h, _ in served) for c in HIT_CLASSES}
    out["hit_classes"] = counts
    log(f"hit classes: {counts}")
    missing = [c for c, n in counts.items() if n == 0]
    if missing or len(served) != len(results) + len(reqs):
        raise RuntimeError(f"hit classes missing {missing} or requests "
                           f"unserved ({len(served)} served)")

    # -- every served image against the fp32 reference ---------------------
    reference = reference_decoder(vae)
    refs: Dict[int, np.ndarray] = {}
    worst = 0
    for oid, _, img in served:
        if oid not in refs:
            z = decompress_latent(box.backend.store.get(oid))
            refs[oid] = reference(z)
        if img.dtype != np.uint8 or img.shape != refs[oid].shape:
            raise RuntimeError(f"object {oid}: served {img.dtype} "
                               f"{img.shape}, want uint8 {refs[oid].shape}")
        worst = max(worst, max_lsb(img, refs[oid]))
    out["max_lsb_vs_reference"] = worst
    log(f"{len(served)} served images of {len(refs)} objects vs the fp32 "
        f"reference: max |diff| {worst} LSB")
    if worst > 1:
        raise RuntimeError(f"served pixels differ from the fp32 reference "
                           f"by {worst} LSB (contract: 1)")

    out["decode_ms"] = time_buckets(vae, latent_hwc, buckets)
    for b, ms in out["decode_ms"].items():
        log(f"decode bucket {b}: {ms:.1f} ms ({ms / b:.1f} ms/image) on "
            f"{dev.device_kind}")
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {out['peak_bytes_in_use']}")
    out["executables_built"] = counter.count
    log(f"executables built: {counter.count} in {counter.seconds:.1f} s")
    box.close()
    return out


def four_chips(decoder: str = "sd35_vae", res: int = 1024,
               buckets: Sequence[int] = BUCKETS, seed: int = 0,
               n_shards: int = 4, n_objects: int = 16) -> Dict:
    """A four-shard box, one shard per device, against the one-shard box
    over the same global fleet (4 x 2 nodes vs 1 x 8) on the same trace;
    raises on any mismatch."""
    import jax
    from repro.launch.serve import serve_windows
    from repro.store import LatentBox
    from repro.vae.model import calibrated_vae

    devices = jax.local_devices()
    if len(devices) < n_shards:
        raise RuntimeError(f"{n_shards} shards need {n_shards} devices, "
                           f"found {len(devices)}")
    vae = calibrated_vae(decoder, seed=seed)
    latent_hwc = vae.cfg.latent_shape(res)
    sharded = LatentBox.engine(vae=vae, config=store_config(res, buckets),
                               shards=n_shards)
    single = LatentBox.engine(vae=vae, config=store_config(
        res, buckets, n_nodes=2 * n_shards))

    shards = sharded.backend.shards
    placed = {}
    for sid, shard in sorted(shards.items()):
        v = shard.backend.engine.vae
        leaf_devs = {d for leaf in jax.tree_util.tree_leaves(v.decoder)
                     for d in leaf.devices()}
        if leaf_devs != {v.device}:
            raise RuntimeError(f"shard {sid}: decoder weights on "
                               f"{leaf_devs}, expected {v.device}")
        placed[sid] = v.device
        log(f"shard {sid} -> device {v.device.id} ({v.device.device_kind})")
    if len({d.id for d in placed.values()}) != n_shards:
        raise RuntimeError(f"shards share devices: {placed}")

    rng = np.random.default_rng(seed)
    for oid in range(n_objects):
        z = rng.standard_normal(latent_hwc).astype(np.float16)
        sharded.put(oid, latent=z)
        single.put(oid, latent=z)

    engines = ([s.backend.engine for s in shards.values()]
               + [single.backend.engine])
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(engines)) as pool:
        list(pool.map(lambda e: e.prewarm_decode(latent_hwc), engines))
    log(f"set-up: {len(engines)} engines x buckets {tuple(buckets)} warmed "
        f"in {time.perf_counter() - t0:.1f} s")

    ids: List[int] = []
    for rep in range(3):                     # full miss, latent, image hit
        ids += list(range(n_objects))
    got, dt_sharded = serve_windows(sharded, ids, WINDOW)
    want, dt_single = serve_windows(single, ids, WINDOW)
    mismatched = [k for k, (a, b) in enumerate(zip(got, want))
                  if a.hit_class != b.hit_class]
    worst = max(max_lsb(a.payload, b.payload) for a, b in zip(got, want))
    per_shard = {sid: s.backend.engine.batcher.stats["decodes"]
                 for sid, s in sorted(shards.items())}
    log(f"{len(ids)} requests: sharded {dt_sharded:.2f} s, one shard "
        f"{dt_single:.2f} s; decodes per shard {per_shard}")
    log(f"classification mismatches: {len(mismatched)}; max pixel "
        f"|diff| vs the one-shard box: {worst} LSB")
    if mismatched:
        raise RuntimeError(f"classification differs at requests "
                           f"{mismatched[:10]}")
    if worst > 1:
        raise RuntimeError(f"sharded pixels differ by {worst} LSB")
    if not all(per_shard.values()):
        raise RuntimeError(f"a shard decoded nothing: {per_shard}")
    return {"decodes_per_shard": per_shard, "max_lsb": worst,
            "requests": len(ids)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded phase only, one shard per chip")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devs[0].platform!r}); this test runs on the chip only",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import device_summary
    log(f"{device_summary()} compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        with tempfile.TemporaryDirectory() as d:
            one_chip(d)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
