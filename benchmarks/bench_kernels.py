"""Kernel-layer microbenchmark: Pallas (interpret) vs jnp oracle
correctness at bench shapes + the analytic HBM-traffic win of each fusion
on the decode hot path, plus the quantized-weight (bf16 / int8 in-kernel
dequant) error sweep and the autotuner's tuned-vs-default A/B
(:mod:`repro.kernels.autotune`).  Rows persist as JSON under artifacts/ (local,
untracked); ``--smoke`` additionally writes ``BENCH_kernels.json`` at the
repo root (the perf-trajectory artifact CI uploads)."""

from __future__ import annotations

import argparse
import os

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import Rows, Timer
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run() -> Rows:
    rows = Rows()
    rng = np.random.default_rng(0)

    # gn+silu fusion: unfused = 2 extra r/w of the activation
    n, h, w, c = 1, 64, 64, 512
    x = jnp.asarray(rng.standard_normal((n, h, w, c)), jnp.float32)
    s = jnp.asarray(rng.standard_normal(c), jnp.float32)
    b = jnp.asarray(rng.standard_normal(c), jnp.float32)
    from repro.kernels.gn_silu import group_norm_silu
    out = group_norm_silu(x, s, b, interpret=True)
    err = float(jnp.abs(out - ref.group_norm_silu_ref(x, s, b)).max())
    rows.add("kernel.gn_silu.max_err", derived=f"{err:.1e}")
    act = n * h * w * c * 4
    rows.add("kernel.gn_silu.traffic_fused_mb", derived=round(3 * act / 1e6, 1))
    rows.add("kernel.gn_silu.traffic_unfused_mb",
             derived=round(5 * act / 1e6, 1))

    # flash attention: removes the S^2 score materialization
    s_len, d = 1024, 64
    q = jnp.asarray(rng.standard_normal((1, 1, s_len, d)), jnp.float32)
    from repro.kernels.flash_attention import flash_attention
    with Timer() as t:
        o = flash_attention(q, q, q, interpret=True, block_q=128,
                            block_kv=128)
    err = float(jnp.abs(o - ref.flash_attention_ref(q, q, q)).max())
    rows.add("kernel.flash_attn.max_err", t.us, f"{err:.1e}")
    s_mid = 128 * 128                    # VAE mid-block at 1024px
    rows.add("kernel.flash_attn.scores_bytes_xla_mb",
             derived=round(3 * s_mid * s_mid * 4 / 1e6, 0))
    rows.add("kernel.flash_attn.scores_bytes_flash_mb", derived=0)

    # conv3x3 implicit GEMM: the kernel's own band sizing at decode shapes
    # (fp32 activations, whole per-step VMEM footprint)
    from repro.kernels.conv3x3 import band_rows, step_vmem_bytes
    for (hh, ww, cin) in ((128, 128, 512), (512, 512, 512), (1024, 1024, 128)):
        rows_band = band_rows(hh, ww, cin, 4, 32)
        vmem = step_vmem_bytes(rows_band, ww, cin, 4) / 2 ** 20
        rows.add(f"kernel.conv3x3.{hh}x{ww}x{cin}.band_rows",
                 derived=rows_band)
        rows.add(f"kernel.conv3x3.{hh}x{ww}x{cin}.vmem_mb",
                 derived=round(vmem, 1))

    # fused gn+silu+conv3x3 (res-block hot path): correctness at decode
    # shapes + the HBM round-trip of the normalized activation it removes
    from repro.kernels.gn_silu_conv import gn_silu_conv3x3
    for (n, hh, ww, cin, cout, g) in ((1, 16, 16, 64, 64, 8),
                                      (2, 8, 8, 32, 64, 8),
                                      (1, 32, 32, 64, 128, 8)):
        x = jnp.asarray(rng.standard_normal((n, hh, ww, cin)), jnp.float32)
        sc = jnp.asarray(rng.standard_normal(cin), jnp.float32)
        bi = jnp.asarray(rng.standard_normal(cin), jnp.float32)
        wt = jnp.asarray(rng.standard_normal((3, 3, cin, cout)) * 0.1,
                         jnp.float32)
        bc = jnp.asarray(rng.standard_normal(cout), jnp.float32)
        with Timer() as t:
            o = gn_silu_conv3x3(x, sc, bi, wt, bc, groups=g, rows=8,
                                interpret=True)
        err = float(jnp.abs(
            o - ref.gn_silu_conv3x3_ref(x, sc, bi, wt, bc, groups=g)).max())
        tag = f"kernel.gn_silu_conv.{n}x{hh}x{ww}x{cin}to{cout}"
        rows.add(f"{tag}.max_err", t.us, f"{err:.1e}")
        act = n * hh * ww * cin * 4
        # unfused: gn+silu writes y, conv re-reads y -> 2 extra activation
        # passes the fusion keeps in VMEM
        rows.add(f"{tag}.traffic_saved_mb", derived=round(2 * act / 1e6, 2))

    # fused nearest-2x upsample + conv3x3 (decoder upsampler): the phase
    # decomposition never materializes the 4x intermediate in HBM and
    # collapses 9 taps over 4x pixels into 16 taps over 1x pixels
    from repro.kernels.upsample_conv import upsample_conv3x3
    for (n, hh, ww, cin, cout) in ((1, 16, 16, 64, 64), (2, 8, 12, 32, 64)):
        x = jnp.asarray(rng.standard_normal((n, hh, ww, cin)), jnp.float32)
        wt = jnp.asarray(rng.standard_normal((3, 3, cin, cout)) * 0.1,
                         jnp.float32)
        bc = jnp.asarray(rng.standard_normal(cout), jnp.float32)
        with Timer() as t:
            o = upsample_conv3x3(x, wt, bc, rows=8, interpret=True)
        err = float(jnp.abs(o - ref.upsample_conv3x3_ref(x, wt, bc)).max())
        tag = f"kernel.upsample_conv.{n}x{hh}x{ww}x{cin}to{cout}"
        rows.add(f"{tag}.max_err", t.us, f"{err:.1e}")
        # unfused: the upsampled [2h, 2w, c] intermediate is written by
        # the repeat and re-read by the conv
        inter = n * 4 * hh * ww * cin * 4
        rows.add(f"{tag}.intermediate_saved_mb",
                 derived=round(2 * inter / 1e6, 2))
        rows.add(f"{tag}.mac_ratio", derived=round(36 / 16, 2))

    # fused output epilogue (GN+SiLU+conv_out+clamp+uint8): the decode's
    # last write is the displayable image at 1/4 the float32 bytes
    from repro.kernels.output_epilogue import output_epilogue
    for (n, hh, ww, cin, g) in ((1, 16, 16, 64, 8), (2, 8, 8, 32, 8)):
        x = jnp.asarray(rng.standard_normal((n, hh, ww, cin)), jnp.float32)
        sc = jnp.asarray(rng.standard_normal(cin), jnp.float32)
        bi = jnp.asarray(rng.standard_normal(cin), jnp.float32)
        wt = jnp.asarray(rng.standard_normal((3, 3, cin, 3)) * 0.1,
                         jnp.float32)
        bc = jnp.asarray(rng.standard_normal(3) * 0.1, jnp.float32)
        with Timer() as t:
            o = output_epilogue(x, sc, bi, wt, bc, groups=g, rows=8,
                                interpret=True)
        want = ref.output_epilogue_ref(x, sc, bi, wt, bc, groups=g)
        lsb = int(np.abs(np.asarray(o, np.int16)
                         - np.asarray(want, np.int16)).max())
        tag = f"kernel.output_epilogue.{n}x{hh}x{ww}x{cin}"
        rows.add(f"{tag}.max_lsb", t.us, lsb)
        rows.add(f"{tag}.out_bytes_ratio_f32_over_u8", derived=4.0)

    # decode attention: streams the KV cache exactly once
    n, hq, hkv, S, d = 2, 8, 2, 512, 64
    q1 = jnp.asarray(rng.standard_normal((n, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((n, hkv, S, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((n, hkv, S, d)), jnp.float32)
    lens = jnp.full((n,), S, jnp.int32)
    from repro.kernels.decode_attention import decode_attention
    o = decode_attention(q1, kc, vc, lens, interpret=True)
    err = float(jnp.abs(o - ref.decode_attention_ref(q1, kc, vc, lens)).max())
    rows.add("kernel.decode_attn.max_err", derived=f"{err:.1e}")
    gqa_reread = hq // hkv
    rows.add("kernel.decode_attn.kv_reads_xla", derived=gqa_reread)
    rows.add("kernel.decode_attn.kv_reads_kernel", derived=1)

    quantized_rows(rows)
    tuned_rows(rows)
    return rows


def quantized_rows(rows: Rows) -> None:
    """weight_dtype sweep per kernel x bucket: in-kernel dequant (bf16 /
    per-channel int8, :mod:`repro.vae.quantize`) vs the f32 oracle at the
    demo decoder's dispatch shapes — us/call and max output error."""
    from repro.kernels import autotune as at
    from repro.vae.model import DEMO_VAE
    for bucket in (1, 2):
        specs = {}
        for s in at.decode_shapes(DEMO_VAE, (8, 8, 4), bucket):
            specs.setdefault(s["kernel"], s)     # one shape per kernel
        for kernel, spec in specs.items():
            oracle = None
            for wd in ("float32", "bfloat16", "int8"):
                thunk = at._make_thunk(spec, wd, "pallas_interpret",
                                       at.DEFAULTS[kernel])
                with Timer() as t:
                    out = np.asarray(jax.block_until_ready(thunk()))
                if wd == "float32":
                    oracle = out
                    continue
                if out.dtype == np.uint8:        # epilogue compares in LSB
                    err = int(np.abs(out.astype(np.int16)
                                     - oracle.astype(np.int16)).max())
                else:
                    err = f"{float(np.abs(out - oracle).max()):.1e}"
                rows.add(f"kernel.quantized.{kernel}.b{bucket}.{wd}.max_err",
                         t.us, err)


def tuned_rows(rows: Rows) -> None:
    """In-bench autotune A/B over the demo decode shapes: the persisted
    winner's us vs the measured default's, from the same sweep.  A winner
    slower than the default can never be recorded silently — candidate 0
    is always the default and ties keep it, and this bench asserts the
    invariant on every entry it emits."""
    from benchmarks.common import ART
    from repro.kernels import autotune as at
    from repro.vae.model import DEMO_VAE
    path = os.path.join(ART, at.CACHE_FILENAME)
    cache = at.TuningCache.load(path)
    if len(cache) == 0:                          # cold: defaults serve
        rows.add("tuning.fallback",
                 derived="cold cache: hand-picked defaults until tuned")
    tuner = at.KernelAutotuner(cache, DEMO_VAE, impl="pallas_interpret",
                               reps=2, rows_grid=(8, 16, 32),
                               block_cout_grid=(32, 64, 128))
    for b in (1, 2):
        tuner.note_bucket(b, (8, 8, 4))
    while tuner.pending:
        tuner.step(4)
    for key, e in sorted(cache.entries.items()):
        assert e["us"] <= e["default_us"], \
            f"tuned {key} regressed vs its own default measurement"
        rows.add(f"kernel.tuned.{key}.us", e["us"],
                 round(e["default_us"] / max(e["us"], 1e-9), 2))
        rows.add(f"kernel.tuned.{key}.default_us", e["default_us"])
    rows.add("kernel.tuned.keys", derived=len(cache))


def trajectory(out_dir: str = REPO_ROOT) -> Rows:
    """The perf-trajectory artifact: ``<out_dir>/BENCH_kernels.json``."""
    rows = run()
    path = rows.save_json("BENCH_kernels", out_dir=out_dir)
    print(f"# saved {path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="interpret-mode kernel sweep; writes "
                         "BENCH_kernels.json at the repo root")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        trajectory().print()
        return
    rows = run()
    rows.print()
    print(f"# saved {rows.save_json('bench_kernels')}")


if __name__ == "__main__":
    main()
