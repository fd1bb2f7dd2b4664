"""Paper Table 1c — decode vs generation cost, plus the PR-4 regeneration
fast path before/after.

No GPU here, so three complementary measurements:

(a) the v5e roofline of our decoder (fused upsampler + uint8 epilogue vs
    the pre-fusion traffic model) — this is the T_decode the cluster
    simulator uses;
(b) a CPU cross-check that the jitted decode scales like the roofline
    says (latency ~ linear in batch);
(c) the **fast-path A/B**: per-image wall clock of the DecodeBatcher at
    each batch bucket, pre-PR baseline (float32 pixels, serialized host
    DEFLATE, ``block_until_ready`` between chunks) vs the fast path
    (uint8 fused-epilogue decode, memoized decompression, pipelined
    async dispatch), interleaved A/B windows so machine noise hits both
    arms equally.  The headline ``decode.fastpath.b8.speedup`` row is the
    acceptance metric recorded in ``BENCH_decode.json``
    (``python -m benchmarks.run --trajectory``).

plus (d) the **quantized-decoder A/B**: bf16 vs f32 ``decode_u8`` per
batch bucket with a freshly tuned kernel cache active, gated at ±1 LSB on
every bucket (``StoreConfig.weight_dtype`` — :mod:`repro.vae.quantize`).

``--smoke`` runs (c)+(d) at reduced repetitions for CI and writes
``BENCH_decode.json`` at the repo root.
"""

from __future__ import annotations

import argparse
import os
import time
import types

import numpy as np

import jax.numpy as jnp

from benchmarks.common import Rows, Timer
from repro.compression.latentcodec import compress_latent
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import DecodeBatcher
from repro.vae.model import VAE, VAEConfig
from repro.vae.serve import decode_ms_estimate

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the fast-path A/B decoder: latents heavy enough that host DEFLATE is a
#: visible fraction of the decode wall (as on the paper's 512 KB blobs),
#: decode small enough for CI
FAST_LATENT = (16, 16, 128)
FAST_CFG = VAEConfig(name="bench_fast", latent_channels=128,
                     block_out_channels=(4, 8), layers_per_block=1, groups=4)


def roofline_rows(rows: Rows) -> None:
    for res in (512, 1024):
        est = decode_ms_estimate(res)                      # fused fast path
        base = decode_ms_estimate(res, fused_upsampler=False,
                                  uint8_output=False)      # pre-PR model
        rows.add(f"decode.v5e.{res}.flops_g", derived=round(est["flops"] / 1e9, 1))
        rows.add(f"decode.v5e.{res}.compute_ms",
                 derived=round(est["compute_ms"], 1))
        rows.add(f"decode.v5e.{res}.memory_ms",
                 derived=round(est["memory_ms"], 1))
        rows.add(f"decode.v5e.{res}.decode_ms",
                 derived=round(est["decode_ms"], 1))
        rows.add(f"decode.v5e.{res}.unfused.decode_ms",
                 derived=round(base["decode_ms"], 1))
        rows.add(f"decode.v5e.{res}.fused_bytes_saved_mb",
                 derived=round((base["bytes"] - est["bytes"]) / 1e6, 1))
    # paper-reported GPU decode times for context
    rows.add("decode.paper.h100_ms", derived=32.6)
    rows.add("decode.paper.rtx5090_ms", derived=47.3)
    rows.add("decode.paper.generation_ms", derived=3905)
    rows.add("decode.ratio_generation_over_decode", derived=round(
        3905 / decode_ms_estimate(1024)["decode_ms"], 0))


def cpu_crosscheck_rows(rows: Rows) -> None:
    # CPU cross-check: small decoder, batch scaling ~ linear (compute-bound)
    cfg = VAEConfig(name="tiny", latent_channels=4,
                    block_out_channels=(32, 64), layers_per_block=1,
                    groups=8)
    vae = VAE(cfg, with_encoder=False)
    times = {}
    for b in (1, 2, 4):
        z = jnp.zeros((b, 16, 16, 4), jnp.float32)
        vae.decode(z).block_until_ready()
        with Timer() as t:
            for _ in range(5):
                vae.decode(z).block_until_ready()
        times[b] = t.us / 5
        rows.add(f"decode.cpu_tiny.b{b}.us", times[b], round(times[b], 0))
    rows.add("decode.cpu_scaling_b4_over_b1",
             derived=round(times[4] / times[1], 2))


def _fastpath_batchers(vae):
    """(baseline, fast): the pre-PR decode path vs the PR-4 fast path."""
    base = DecodeBatcher(vae, (1, 2, 4, 8), pixel_format="float32",
                         pipeline=False, memo_entries=0)
    fast = DecodeBatcher(vae, (1, 2, 4, 8), pixel_format="uint8",
                         pipeline=True, memo_entries=256)
    base.prewarm(FAST_LATENT)
    fast.prewarm(FAST_LATENT)
    return base, fast


def fastpath_rows(rows: Rows, reps: int = 12) -> None:
    """Interleaved A/B of the regeneration fast path per batch bucket."""
    vae = VAE(FAST_CFG, with_encoder=False)
    rng = np.random.default_rng(0)
    n_oids = 16
    blobs = {i: compress_latent(
        rng.standard_normal(FAST_LATENT).astype(np.float16))
        for i in range(n_oids)}
    node = types.SimpleNamespace(tuner=None)       # no tuner in the bench

    with Timer() as t:
        for _ in range(5):
            from repro.compression.latentcodec import decompress_latent
            decompress_latent(blobs[0])
    rows.add("decode.fastpath.blob_kb", derived=round(len(blobs[0]) / 1e3, 1))
    rows.add("decode.fastpath.decompress_ms", derived=round(t.us / 5 / 1e3, 3))

    base, fast = _fastpath_batchers(vae)

    def windows(batcher, oids, reps):
        """Median per-image ms over repeated serving windows (steady
        state: repeat traffic, so the memo is allowed to work)."""
        samples = []
        for _ in range(reps):
            for i in oids:
                batcher.submit(i, blobs[i], node)
            t0 = time.perf_counter()
            batcher.flush()
            samples.append((time.perf_counter() - t0) * 1e3 / len(oids))
        return samples

    # per-bucket sweep: windows of exactly b oids -> one bucket-b chunk
    for b in (1, 2, 4):
        oids = list(range(b))
        sb, sf = [], []
        for _ in range(reps):                      # interleave the arms
            sb += windows(base, oids, 1)
            sf += windows(fast, oids, 1)
        mb, mf = np.median(sb[1:]), np.median(sf[1:])
        rows.add(f"decode.fastpath.b{b}.base_per_image_ms",
                 derived=round(float(mb), 3))
        rows.add(f"decode.fastpath.b{b}.fast_per_image_ms",
                 derived=round(float(mf), 3))
        rows.add(f"decode.fastpath.b{b}.speedup",
                 derived=round(float(mb / mf), 2))

    # the batch-8 bucket (acceptance metric): 16-oid windows = two
    # bucket-8 chunks, so codec/decode pipelining is live
    oids = list(range(n_oids))
    sb, sf = [], []
    for _ in range(reps):
        sb += windows(base, oids, 1)
        sf += windows(fast, oids, 1)
    mb, mf = np.median(sb[2:]), np.median(sf[2:])
    rows.add("decode.fastpath.b8.base_per_image_ms",
             derived=round(float(mb), 3))
    rows.add("decode.fastpath.b8.fast_per_image_ms",
             derived=round(float(mf), 3))
    rows.add("decode.fastpath.b8.speedup", derived=round(float(mb / mf), 2))

    # pixel-tier byte economics of the two formats at this decoder's
    # output shape (what the DualFormatCache now actually charges)
    h = FAST_LATENT[0] * 2 ** (len(FAST_CFG.block_out_channels) - 1)
    u8 = float(h * h * 3)
    rows.add("decode.pixel_bytes_per_object.uint8", derived=u8)
    rows.add("decode.pixel_bytes_per_object.float32", derived=u8 * 4)
    rows.add("decode.pixel_bytes_per_object.ratio", derived=4.0)
    rows.add("decode.fastpath.memo_hits", derived=fast.stats["memo_hits"])
    rows.add("decode.fastpath.decompressions",
             derived=fast.stats["decompressions"])


def quantized_rows(rows: Rows, smoke: bool = False) -> None:
    """bf16-vs-f32 ``decode_u8`` A/B per bucket, run with a freshly tuned
    kernel cache active: per-image ms of both arms from the same run,
    plus the ±1-LSB gate asserted on every bucket (the admission contract
    of ``StoreConfig.weight_dtype`` — :mod:`repro.vae.quantize`)."""
    from repro.kernels import autotune as at
    from repro.vae import quantize as Q
    from repro.vae.model import calibrated_vae
    latent, buckets = (8, 8, 4), (1, 2, 4, 8)
    vae = calibrated_vae("demo", seed=0, weight_dtype="bfloat16")
    st = Q.decoder_storage(vae._params_for("bfloat16"))
    rows.add("decode.quantized.bf16_bytes_per_param",
             derived=round(st["bytes_per_param"], 2))
    # tune the decode shape set first, so the A/B serves tuned blockings
    cache = at.TuningCache(None)
    tuner = at.KernelAutotuner(cache, vae.cfg, weight_dtype="bfloat16",
                               impl="pallas_interpret", reps=1,
                               rows_grid=(8, 16), block_cout_grid=(32, 64))
    for b in buckets:
        tuner.note_bucket(b, latent)
    while tuner.pending:
        tuner.step(8)
    rows.add("decode.quantized.tuned_keys", derived=len(cache))
    reps = 3 if smoke else 8
    with at.active_cache(cache):
        vae.refresh_kernels()               # retrace under the tuned cache
        lsb = Q.gate_max_lsb(vae, buckets, latent)
        for b in buckets:
            assert lsb[b] <= 1, f"bucket {b} breaches the gate: {lsb[b]} LSB"
            z = Q.probe_latents(latent, b, seed=5)
            for prec in ("float32", None):  # warm both arms
                vae.decode_u8(jnp.asarray(z), precision=prec
                              ).block_until_ready()
            tf, tq = [], []
            for _ in range(reps):           # interleave the arms
                t0 = time.perf_counter()
                vae.decode_u8(jnp.asarray(z),
                              precision="float32").block_until_ready()
                tf.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                vae.decode_u8(jnp.asarray(z)).block_until_ready()
                tq.append(time.perf_counter() - t0)
            mf = float(np.median(tf)) * 1e3
            mq = float(np.median(tq)) * 1e3
            rows.add(f"decode.quantized.b{b}.f32_ms", derived=round(mf, 3))
            rows.add(f"decode.quantized.b{b}.bf16_ms", derived=round(mq, 3))
            rows.add(f"decode.quantized.b{b}.speedup",
                     derived=round(mf / max(mq, 1e-9), 2))
            rows.add(f"decode.quantized.b{b}.max_lsb", derived=lsb[b])
    vae.refresh_kernels()                   # drop cache-bound compilations


def run(smoke: bool = False) -> Rows:
    rows = Rows()
    roofline_rows(rows)
    if not smoke:
        cpu_crosscheck_rows(rows)
    fastpath_rows(rows, reps=4 if smoke else 12)
    quantized_rows(rows, smoke=smoke)
    return rows


def trajectory(out_dir: str = REPO_ROOT, smoke: bool = False) -> Rows:
    """The perf-trajectory artifact: ``<out_dir>/BENCH_decode.json``."""
    rows = run(smoke=smoke)
    path = rows.save_json("BENCH_decode", out_dir=out_dir)
    print(f"# saved {path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fast-path A/B; writes BENCH_decode.json "
                         "at the repo root")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        trajectory(smoke=True).print()
        return
    rows = run()
    rows.print()
    print(f"# saved {rows.save_json('bench_decode')}")


if __name__ == "__main__":
    main()
