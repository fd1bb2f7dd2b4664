"""Jitted entry points for the kernel layer with implementation dispatch.

``impl`` is one of
  'xla'               pure-jnp reference (the oracle)
  'pallas'            Pallas TPU kernel (Mosaic; requires TPU)
  'pallas_interpret'  Pallas kernel body interpreted on CPU (correctness)

``impl=None`` is chosen from the platform: 'pallas' when JAX's default
backend is a TPU, 'xla' elsewhere — so the served decode runs the Pallas
kernels on the chip with no flag threaded through the models, and there
is no quiet fallback to the oracle there.  Kernel tests pin
'pallas_interpret'; reference checks pass 'xla' explicitly.

Two cross-cutting paths live at this layer (not inside individual
kernels), so every consumer gets them uniformly:

* **weight dtype** — the decode-path kernels (``conv3x3``,
  ``gn_silu_conv3x3``, ``upsample_conv3x3``, ``output_epilogue``) accept
  their conv weight as a plain array (float32 or bfloat16 storage, cast
  to fp32 per tap tile inside the kernel) or as a
  :class:`QuantizedWeight` (int8 storage + per-output-channel fp32
  scale, dequantized on the fly in VMEM) — the dequantized fp32 copy
  never exists in HBM.  See :mod:`repro.vae.quantize` for the parameter
  conversion and the ±1-LSB serving gate.
* **autotuned block shapes** — the Pallas paths consult the process
  tuning cache (:mod:`repro.kernels.autotune`) keyed on
  ``(kernel, call shape, weight dtype)`` and pass any tuned
  ``rows``/``block_cout`` through as static kernel parameters; with no
  cache installed (or on a cache miss) the hand-picked defaults apply
  unchanged.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import ref

_VALID = ("xla", "pallas", "pallas_interpret")


def platform_impl() -> str:
    """The implementation ``impl=None`` resolves to on this process's
    default backend."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str], kernel: str) -> str:
    impl = impl or platform_impl()
    if impl not in _VALID:
        raise ValueError(
            f"{kernel}: impl must be one of {_VALID}, got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# quantized weight container
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """int8 weight storage + per-output-channel fp32 dequant scale.

    ``q`` keeps the tensor's original shape in int8; ``scale`` is
    ``[cout]`` (the last axis).  The logical value is ``q * scale`` —
    kernels consume ``q`` directly and fold the scale into the fp32
    accumulator (one multiply per output tile), so the dequantized fp32
    weight never materializes in HBM.  Registered as a pytree so
    parameter trees holding it pass through ``jax.jit`` transparently.
    """

    def __init__(self, q: jax.Array, scale: jax.Array):
        self.q = q
        self.scale = scale

    # array-like surface so parameter trees can be inspected uniformly
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes) + int(self.scale.nbytes)

    @property
    def size(self) -> int:
        return int(self.q.size)

    def dequant(self, dtype=jnp.float32) -> jax.Array:
        """The logical fp tensor (oracle paths only — kernels never call
        this; they dequantize per tile in VMEM)."""
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self) -> str:
        return (f"QuantizedWeight(shape={tuple(self.q.shape)}, "
                f"scale[{self.scale.shape[0]}])")


def weight_dtype_of(w) -> str:
    """The storage-dtype tag of a kernel weight ('float32' | 'bfloat16'
    | 'int8') — the autotuning-cache key component."""
    if isinstance(w, QuantizedWeight):
        return "int8"
    return str(jnp.asarray(w).dtype)


def _weight_parts(w):
    """(kernel weight array, per-cout scale or None) for dispatch."""
    if isinstance(w, QuantizedWeight):
        return w.q, w.scale
    return w, None


def _note_path(kernel: str, x, wq, tuned) -> None:
    """Count the traced call site of a banded conv in
    :data:`repro.kernels.conv3x3.PATHS` by the path its band takes.  Here,
    at trace time of the caller: inside the jitted wrappers a Python side
    effect would run once per distinct shape, since JAX reuses an inner
    ``jit``'s trace, not once per conv of the decode."""
    from repro.kernels import conv3x3 as c3
    knobs = tuned or autotune.DEFAULTS[kernel]
    path = c3.plan(x.shape, wq.shape[-1], x.dtype.itemsize, knobs["rows"],
                   knobs["block_cout"], kernel in autotune.GN_FUSED)[2]
    c3.PATHS[path] += 1


def _dequant(w, dtype=jnp.float32):
    if isinstance(w, QuantizedWeight):
        return w.dequant(dtype)
    return w


# ---------------------------------------------------------------------------

def group_norm_silu(x, scale, bias, groups: int = 32, eps: float = 1e-6,
                    impl: Optional[str] = None):
    impl = _resolve(impl, "group_norm_silu")
    if impl == "xla":
        return ref.group_norm_silu_ref(x, scale, bias, groups, eps)
    from repro.kernels import gn_silu
    return gn_silu.group_norm_silu(x, scale, bias, groups=groups, eps=eps,
                                   interpret=impl == "pallas_interpret")


def gn_silu_conv3x3(x, scale, bias, w, b=None, groups: int = 32,
                    eps: float = 1e-6, impl: Optional[str] = None):
    """Fused GroupNorm + SiLU + 3x3 SAME conv (the res-block hot path)."""
    impl = _resolve(impl, "gn_silu_conv3x3")
    if impl == "xla":
        return ref.gn_silu_conv3x3_ref(x, scale, bias, _dequant(w), b,
                                       groups, eps)
    from repro.kernels import gn_silu_conv as gsc
    wq, w_scale = _weight_parts(w)
    tuned = autotune.tuned_params("gn_silu_conv3x3", x.shape, wq.shape[-1],
                                  weight_dtype_of(w))
    _note_path("gn_silu_conv3x3", x, wq, tuned)
    return gsc.gn_silu_conv3x3(x, scale, bias, wq, b, groups=groups, eps=eps,
                               w_scale=w_scale,
                               interpret=impl == "pallas_interpret", **tuned)


def upsample_conv3x3(x, w, b=None, impl: Optional[str] = None):
    """Fused nearest-2x upsample + 3x3 SAME conv (the decoder upsampler);
    the Pallas kernel never materializes the 4x upsampled intermediate."""
    impl = _resolve(impl, "upsample_conv3x3")
    if impl == "xla":
        return ref.upsample_conv3x3_ref(x, _dequant(w), b)
    from repro.kernels import upsample_conv as uc
    wq, w_scale = _weight_parts(w)
    tuned = autotune.tuned_params("upsample_conv3x3", x.shape, wq.shape[-1],
                                  weight_dtype_of(w))
    return uc.upsample_conv3x3(x, wq, b, w_scale=w_scale,
                               interpret=impl == "pallas_interpret", **tuned)


def output_epilogue(x, scale, bias, w, b=None, groups: int = 32,
                    eps: float = 1e-6, impl: Optional[str] = None):
    """Fused GN + SiLU + conv_out + clamp + uint8 quantize — the decode's
    final stage, returning displayable uint8 HWC pixels."""
    impl = _resolve(impl, "output_epilogue")
    if impl == "xla":
        return ref.output_epilogue_ref(x, scale, bias, _dequant(w), b,
                                       groups, eps)
    from repro.kernels import output_epilogue as oe
    wq, w_scale = _weight_parts(w)
    tuned = autotune.tuned_params("output_epilogue", x.shape, wq.shape[-1],
                                  weight_dtype_of(w))
    _note_path("output_epilogue", x, wq, tuned)
    return oe.output_epilogue(x, scale, bias, wq, b, groups=groups, eps=eps,
                              w_scale=w_scale,
                              interpret=impl == "pallas_interpret", **tuned)


def rms_output_epilogue(x, scale, bias, w, b=None, eps: float = 1e-5,
                        impl: Optional[str] = None):
    """RMSNorm + ReLU + conv_out + clamp + uint8 quantize — the DC-AE
    decode's final stage, returning displayable uint8 HWC pixels."""
    impl = _resolve(impl, "rms_output_epilogue")
    if impl == "xla":
        return ref.rms_output_epilogue_ref(x, scale, bias, _dequant(w), b,
                                           eps)
    from repro.kernels import output_epilogue as oe
    wq, w_scale = _weight_parts(w)
    tuned = autotune.tuned_params("rms_output_epilogue", x.shape,
                                  wq.shape[-1], weight_dtype_of(w))
    _note_path("rms_output_epilogue", x, wq, tuned)
    return oe.rms_output_epilogue(x, scale, bias, wq, b, eps=eps,
                                  w_scale=w_scale,
                                  interpret=impl == "pallas_interpret",
                                  **tuned)


def linear_attention(sources, head_dim: int, eps: float = 1e-15,
                     impl: Optional[str] = None):
    """ReLU linear attention over packed part-major ``[N, T, 3 * Cq]``
    sources -> ``[N, T, S * Cq]`` (:mod:`repro.kernels.linear_attention`)."""
    impl = _resolve(impl, "linear_attention")
    sources = tuple(sources)
    if impl == "xla":
        return ref.linear_attention_ref(sources, head_dim, eps)
    from repro.kernels import linear_attention as la
    la.TRACED["linear_attention"] += 1        # per call site, at trace time
    return la.linear_attention(sources, head_dim=head_dim, eps=eps,
                               interpret=impl == "pallas_interpret")


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    window: Optional[int] = None, impl: Optional[str] = None):
    impl = _resolve(impl, "flash_attention")
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale,
                                       window=window)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, scale=scale,
                              window=window,
                              interpret=impl == "pallas_interpret")


def decode_attention(q, k_cache, v_cache, lengths, scale=None,
                     impl: Optional[str] = None):
    impl = _resolve(impl, "decode_attention")
    if impl == "xla":
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k_cache, v_cache, lengths, scale=scale,
                               interpret=impl == "pallas_interpret")


def conv3x3(x, w, b=None, impl: Optional[str] = None):
    impl = _resolve(impl, "conv3x3")
    if impl == "xla":
        return ref.conv3x3_ref(x, _dequant(w), b)
    from repro.kernels import conv3x3 as c3
    wq, w_scale = _weight_parts(w)
    tuned = autotune.tuned_params("conv3x3", x.shape, wq.shape[-1],
                                  weight_dtype_of(w))
    _note_path("conv3x3", x, wq, tuned)
    return c3.conv3x3(x, wq, b, w_scale=w_scale,
                      interpret=impl == "pallas_interpret", **tuned)


def rwkv6_scan(r, k, v, w, u, state=None, impl: Optional[str] = None):
    impl = _resolve(impl, "rwkv6_scan")
    if impl == "xla":
        return ref.rwkv6_scan_ref(r, k, v, w, u, state)
    from repro.kernels import rwkv6_scan as rs
    return rs.rwkv6_scan(r, k, v, w, u, state,
                         interpret=impl == "pallas_interpret")
