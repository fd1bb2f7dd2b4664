"""Decoder weight quantization + the ±1-LSB serving gate.

The decode path is weight-bandwidth-light but every byte of decoder
params is resident per device; quantized storage halves (bf16) or
quarters (int8) that footprint and — because the kernel layer
dequantizes on the fly in VMEM (:mod:`repro.kernels.ops`) — the fp32
copy never reappears in HBM.

Storage policy per ``weight_dtype``:

=========  ==========================================================
float32    identity (the oracle).
bfloat16   every >=2-D weight (convs + attention denses) cast to
           bf16; biases and GN affine stay fp32.  ~2 bytes/param.
int8       4-D conv weights -> :class:`QuantizedWeight` (symmetric
           per-output-channel scale, fp32 accumulate); 2-D denses
           stay bf16 (attention is a tiny fraction of the params and
           per-channel scales don't fit the matmul epilogue cheaply).
           ~1 byte/param on the conv-dominated decoder.
=========  ==========================================================

**The gate.**  Quantization is only admitted behind the same contract
PR 4's fused kernels shipped under: the uint8 fast path may differ from
the f32-weight oracle by at most ±1 LSB on *every* batch bucket
(:func:`check_u8_gate`); the engine runs the check at open time and
rejects the config otherwise.  bf16 passes on decoders with in-display-
range outputs; int8 is opt-in precisely because the gate — not a
promise — decides per stack whether 8-bit storage is pixel-safe.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import QuantizedWeight

WEIGHT_DTYPES = ("float32", "bfloat16", "int8")

#: Nominal storage cost (bytes/param) per mode on the conv-dominated
#: decoder — the README knob table; measure real trees with
#: :func:`decoder_storage`.
BYTES_PER_PARAM = {"float32": 4.0, "bfloat16": 2.0, "int8": 1.0}


class QuantizationGateError(ValueError):
    """A quantized decoder breached the ±1-LSB uint8 output gate (the
    config is rejected; serving stays on the f32 oracle)."""


# ---------------------------------------------------------------------------
# array-level quantizers
# ---------------------------------------------------------------------------

def quantize_int8(w) -> QuantizedWeight:
    """Symmetric per-output-channel int8: ``scale[c] = max|w[..., c]| /
    127``, fp32 dequant in the kernel accumulator."""
    w = jnp.asarray(w, jnp.float32)
    axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantizedWeight(q, scale)


def _map_weights(tree, fn: Callable[[Any], Any]):
    if isinstance(tree, dict):
        return {k: _map_weights(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_map_weights(v, fn) for v in tree]
    return fn(tree)


def _to_bf16(p):
    return p.astype(jnp.bfloat16) if getattr(p, "ndim", 0) >= 2 else p


def _to_int8(p):
    nd = getattr(p, "ndim", 0)
    if nd == 4:
        return quantize_int8(p)
    if nd >= 2:
        return p.astype(jnp.bfloat16)
    return p


#: ``weight_dtype -> params tree transform``.  A registry (not a match
#: statement) so tests can install an out-of-tolerance fake quantizer and
#: prove the gate rejects it.
QUANTIZERS: Dict[str, Callable[[Any], Any]] = {
    "float32": lambda params: params,
    "bfloat16": lambda params: _map_weights(params, _to_bf16),
    "int8": lambda params: _map_weights(params, _to_int8),
}


def quantize_decoder(params, weight_dtype: str):
    """The ``weight_dtype`` storage form of a decoder param tree (the
    fp32 input tree is left untouched — it remains the gate's oracle)."""
    try:
        quantizer = QUANTIZERS[weight_dtype]
    except KeyError:
        raise ValueError(
            f"weight_dtype must be one of {tuple(QUANTIZERS)}: "
            f"{weight_dtype!r}") from None
    return quantizer(params)


def decoder_storage(params) -> Dict[str, float]:
    """Measured storage of a (possibly quantized) param tree."""
    nbytes = 0
    count = 0
    leaves = []
    _map_weights(params, leaves.append)
    for p in leaves:
        nbytes += int(p.nbytes)
        count += int(p.size)
    return {"bytes": float(nbytes), "params": float(count),
            "bytes_per_param": nbytes / max(count, 1)}


# ---------------------------------------------------------------------------
# the ±1-LSB uint8 output gate
# ---------------------------------------------------------------------------

def probe_latents(latent_hwc: Tuple[int, int, int], bucket: int,
                  seed: int = 0) -> np.ndarray:
    """Deterministic unit-normal probe latents (the encoder normalizes
    latents to ~unit scale, so this is the serving operating point)."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((bucket,) + tuple(latent_hwc)
                               ).astype(np.float32)


def gate_max_lsb(vae, buckets: Sequence[int],
                 latent_hwc: Tuple[int, int, int],
                 seed: int = 0) -> Dict[int, int]:
    """Per-bucket max |uint8 difference| between the quantized and the
    f32-oracle ``decode_u8`` on shared probe latents."""
    out: Dict[int, int] = {}
    for b in sorted(set(int(x) for x in buckets)):
        z = probe_latents(latent_hwc, b, seed)
        ref = np.asarray(vae.decode_u8(jnp.asarray(z), precision="float32"))
        got = np.asarray(vae.decode_u8(jnp.asarray(z)))
        out[b] = int(np.max(np.abs(ref.astype(np.int16)
                                   - got.astype(np.int16))))
    return out


def check_u8_gate(vae, buckets: Sequence[int],
                  latent_hwc: Tuple[int, int, int], seed: int = 0,
                  tol: int = 1) -> Dict[int, int]:
    """Run the gate; returns the per-bucket max LSB error, raising
    :class:`QuantizationGateError` if any bucket exceeds ``tol``."""
    lsb = gate_max_lsb(vae, buckets, latent_hwc, seed=seed)
    bad = {b: v for b, v in lsb.items() if v > tol}
    if bad:
        raise QuantizationGateError(
            f"weight_dtype={vae.weight_dtype!r} breaches the +-{tol}-LSB "
            f"uint8 gate on bucket(s) {bad} (per-bucket max LSB: {lsb}); "
            f"config rejected — serve float32 weights or a gentler "
            f"weight_dtype")
    return lsb


# ---------------------------------------------------------------------------
# test/bench fixtures
# ---------------------------------------------------------------------------

def calibrate_output_range(vae, target_std: float = 0.35,
                           probe_hw: int = 8, seed: int = 0) -> float:
    """Rescale ``conv_out`` in place so probe decodes land inside the
    display range (std ``target_std`` on [-1, 1]).

    Random-init decoders emit std ~0.6 / |max| ~3.5 images that saturate
    the uint8 clamp, which makes gate measurements unrepresentative of
    trained decoders (whose outputs are in-range by construction, and
    whose quantization error scales with output magnitude).  Tests and
    benches use this to emulate trained output statistics; returns the
    applied gain."""
    cfg = vae.cfg
    z = jnp.asarray(probe_latents(
        (probe_hw, probe_hw, cfg.latent_channels), 2, seed))
    y = np.asarray(vae.decode(z))
    gain = float(target_std / max(float(y.std()), 1e-6))
    co = vae.decoder["conv_out"]
    co["w"] = co["w"] * gain
    co["b"] = co["b"] * gain
    vae.set_weight_dtype(vae.weight_dtype)      # re-derive quantized params
    return gain


def snap_to_grid(vae) -> None:
    """Snap the decoder's weights (in place) onto their quantized-storage
    grids — 4-D convs onto the symmetric int8 grid, other >=2-D weights
    onto bf16 — so int8/bf16 quantization round-trips *exactly* (0-LSB
    gate).  A test fixture: it turns the gate into a pure storage/plumbing
    check with no approximation error in the way."""
    def snap(p):
        nd = getattr(p, "ndim", 0)
        if nd == 4:
            return quantize_int8(p).dequant(jnp.float32)
        if nd >= 2:
            return p.astype(jnp.bfloat16).astype(jnp.float32)
        return p
    vae.decoder = _map_weights(vae.decoder, snap)
    vae.set_weight_dtype(vae.weight_dtype)
