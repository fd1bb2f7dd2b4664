"""On-chip benchmark of LatentBox's served read path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one run: it builds the cell's store (configuration
``chipbench/configs/<config>.json``, traffic
``chipbench/mixes/<traffic>.json``, both found by the names in
``BENCHMARK.json``), makes weights and latents from ``--seed``, puts the
objects, compiles and warms every decode bucket, serves the mix's warm
prefix, then measures one window of ``--seconds`` through
``LatentBox.get_many``.  Afterwards it checks a seeded sample of the
served images against the plain reference and prints, as the last line
of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones, read from a profiler trace of the
window), ``device``, ``breakdown`` (with ``--trace 1``) and ``checks``.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from typing import Dict, Optional, Sequence  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import check, flops, profile, serving  # noqa: E402
from lib.cells import Cell, load_cell  # noqa: E402
from lib.peaks import peaks_for  # noqa: E402
from lib.traffic import make_traffic  # noqa: E402
from lib.weights import decoder_weights, freeze, latent  # noqa: E402

#: How long requests due in an open-loop window may drain after it.
DRAIN_S = 30.0
#: The program's decoder sizes that must agree with the configuration.
ARCH_KEYS = {"latent_channels": "latent_channels",
             "block_out_channels": "block_out_channels",
             "layers_per_block": "layers_per_block",
             "groups": "norm_num_groups",
             "scaling_factor": "scaling_factor",
             "shift_factor": "shift_factor"}


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def parse(argv: Optional[Sequence[str]]):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where it is set)."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_vae(cell: Cell, weights, precision: str):
    """The system's VAE with the benchmark's weights loaded, after
    checking that its named decoder has the configuration's sizes."""
    from repro.vae.model import CONFIGS, VAE
    vcfg = CONFIGS[cell.config["decoder"]]
    arch = cell.config["architecture"]
    for ours, theirs in ARCH_KEYS.items():
        a, b = getattr(vcfg, ours), arch[theirs]
        if (list(a) if isinstance(a, tuple) else a) != b:
            raise ValueError(f"decoder {vcfg.name}: {ours}={a!r} but the "
                             f"configuration states {theirs}={b!r}")
    vae = VAE(vcfg, seed=0, with_encoder=False)
    vae.decoder = weights
    vae.set_weight_dtype(precision)
    return vae


def build_box(cell: Cell, vae, latent_bytes: int):
    from repro.core.tuner import TunerConfig
    from repro.store import LatentBox, StoreConfig
    s = cell.config["store"]
    res = cell.config["architecture"]["resolution"]
    image_bytes = float(res * res * cell.config["architecture"]
                        ["out_channels"])
    cfg = StoreConfig(
        n_nodes=int(s["n_nodes"]),
        cache_bytes_per_node=float(s["cache_bytes_per_node"]),
        alpha0=float(s["alpha0"]), tau=float(s["tau"]),
        promote_threshold=int(s["promote_threshold"]),
        image_bytes=image_bytes, latent_bytes=float(latent_bytes),
        pixel_format=s["pixel_format"],
        tuner=TunerConfig(window=int(s["tuner_window"]),
                          step=float(s["tuner_step"])),
        decode_buckets=tuple(int(b) for b in s["decode_buckets"]))
    return LatentBox.engine(vae=vae, config=cfg)


def latent_hwc(cell: Cell):
    arch = cell.config["architecture"]
    s = arch["resolution"] // 2 ** (len(arch["block_out_channels"]) - 1)
    return (s, s, arch["latent_channels"])


def decode_scratch_bytes(vae, buckets, hwc) -> Dict[int, int]:
    """The temporaries of each warmed decode executable, by the compiler's
    count.  The allocator's ``peak_bytes_in_use`` leaves them out, so the
    device's high-water mark is that peak plus the largest of these."""
    params = vae._params_for(None)
    out = {}
    for b in buckets:
        z = vae.place(np.zeros((int(b),) + tuple(hwc), np.float32))
        mem = vae._decode_u8.lower(params, z).compile().memory_analysis()
        out[int(b)] = int(mem.temp_size_in_bytes)
    return out


def end_to_end(cell: Cell, window, setup_s: float) -> Dict[str, float]:
    lat = window.latency_all_s
    out = {"setup_s": setup_s}
    if len(lat):
        out["read_p90_ms"] = serving.percentile_ms(lat, 90)
        out["read_p50_ms"] = serving.percentile_ms(lat, 50)
    if window.end_s > 0:
        out["served_rps"] = int(window.served.sum()) / window.end_s
    return out


def run(args, cell: Cell, require_tpu: bool = True,
        precision: Optional[str] = None, patch=None,
        compile_cache: bool = True) -> Optional[Dict]:
    """One run; returns the result object (None when refused).

    ``precision`` overrides the configuration's serving precision (the
    control), and ``patch(vae, box)``, called once the store is built,
    may plant a fault in the system under test; tests use both."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        log(f"refused: the cell needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    cache = enable_compile_cache(ROOT) if compile_cache else None
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    counter = serving.CompileCounter()
    arch = cell.config["architecture"]
    hwc = latent_hwc(cell)
    precision = precision or cell.config["precision"]
    log(f"{cell.name}: platform={dev.platform} kind={dev.device_kind} "
        f"devices={len(devs)} seed={args.seed} compile_cache={cache}")

    # -- set-up ---------------------------------------------------------------
    from repro.compression.latentcodec import compress_latent
    traffic = make_traffic(cell.mix, args.seed, args.seconds)
    weights = decoder_weights(arch, cell.config["weights"], args.seed, dev)
    vae = program_vae(cell, weights, precision)
    blob = compress_latent(latent(args.seed, int(traffic.objects[0]), hwc))
    box = build_box(cell, vae, len(blob))
    if patch is not None:
        patch(vae, box)
    for oid in traffic.objects:
        box.put(int(oid), latent=latent(args.seed, int(oid), hwc))
    warm = box.backend.engine.prewarm_decode(hwc)
    serving.serve_prefix(box, traffic.prefix, traffic.window)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: {len(traffic.objects)} objects put "
        f"({len(blob)} B per latent), buckets warmed "
        f"{ {b: round(s, 3) for b, s in warm.items()} }, "
        f"{len(traffic.prefix)} prefix requests")

    # -- the window -----------------------------------------------------------
    compiles_before = counter.count
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    if trace_dir:
        profile.start(trace_dir)
    with serving.span("window", bool(args.trace)):
        if traffic.loop == "open":
            window = serving.serve_open(
                box, traffic.ids, traffic.due_s, traffic.window,
                args.seconds, DRAIN_S, trace=bool(args.trace))
        else:
            window = serving.serve_closed(
                box, traffic.ids, traffic.window, args.seconds,
                trace=bool(args.trace))
    if trace_dir:
        profile.stop()
    window_compiles = counter.count - compiles_before
    counter.close()
    stats = dev.memory_stats() or {}
    t0 = time.perf_counter()
    scratch = decode_scratch_bytes(vae, box.backend.engine.batcher.buckets,
                                   hwc)
    memory_peak = (int(stats.get("peak_bytes_in_use", 0))
                   + max(scratch.values()))
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} B; "
        f"decode temporaries by bucket {scratch} B (read in "
        f"{time.perf_counter() - t0:.3f} s); memory_peak_bytes "
        f"{memory_peak} B")

    classes: Dict[str, int] = {}
    for r in window.results:
        if r is not None:
            classes[r.hit_class] = classes.get(r.hit_class, 0) + 1
    late = window.wake_late_s
    quarters = [float(np.median(q)) * 1e3 for q in
                np.array_split(window.latency_all_s, 4) if len(q)]
    log(f"window: {len(window.ids)} requests attempted, "
        f"{int(window.served.sum())} served, {len(window.calls)} calls, "
        f"ended at {window.end_s:.3f} s; hit classes {classes}; compiles "
        f"inside the window: {window_compiles}; generator woke late by "
        f"{(max(late) if late else 0.0) * 1e3:.3f} ms at most, "
        f"{(np.mean(late) if late else 0.0) * 1e3:.3f} ms on average; "
        f"median latency by quarter of the window "
        f"{[round(q, 3) for q in quarters]} ms")

    # -- free the program's state, then the reference -------------------------
    buckets = list(box.backend.engine.batcher.buckets)
    del box, vae
    gc.collect()
    trace_summary = None
    if trace_dir:
        trace_summary = profile.reduce_dir(trace_dir)
        profile.remove(trace_dir)
    ref_mod = cell.reference()
    ref_fn = ref_mod.compiled(freeze(arch), cell.config["precision"])

    def reference(z):
        zb = jax.device_put(z[None], dev)
        return np.asarray(ref_fn(weights, zb))[0]

    k = int(cell.config["check"]["sample_requests"])
    sample = check.choose_sample(window, buckets, args.seed, k)
    t0 = time.perf_counter()
    numbers = check.compare(window, sample, reference, args.seed, hwc)
    res = arch["resolution"]
    numbers["bad_payloads"] = float(check.bad_payloads(
        window, (res, res, arch["out_channels"])))
    log(f"reference: {int(numbers['compared_images'])} served images of "
        f"{int(numbers['compared_objects'])} objects compared in "
        f"{time.perf_counter() - t0:.3f} s; largest difference "
        f"{int(numbers['max_lsb'])} LSB")
    limits = cell.config["check"]["limits"]
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    correct = (numbers["compared_images"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    # -- metrics --------------------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct),
              "attempted": int(len(window.ids)),
              "failed": int((~window.served).sum())}
    if args.trace:
        ctx = types.SimpleNamespace(
            cell=cell, window=window, trace=trace_summary, peaks=peaks,
            calls=flops.decoder_calls(arch),
            flops_per_image=flops.decode_flops_per_image(arch))
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if trace_summary is not None:
            device["busy_s"] = trace_summary.busy_s
            device["window_s"] = trace_summary.window_s
        result["device"] = device
        if trace_summary is not None:
            result["breakdown"] = trace_summary.breakdown()
    else:
        values = end_to_end(cell, window, setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if m["name"] in values}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv: Optional[Sequence[str]] = None, root: str = ROOT,
         require_tpu: bool = True) -> int:
    args = parse(argv)
    cell = load_cell(args.workload, root)
    result = run(args, cell, require_tpu=require_tpu)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
