"""Config-driven VAE (SD/FLUX family) — the reconstruction engine of the
latent-first store (paper §2.2).

Decoder matches the SD 3.5 / FLUX.1 shape: 16 latent channels at 1/8
spatial resolution, block_out_channels (128, 256, 512, 512), 3 res blocks
per decoder level, one single-head attention mid-block — ~49.5 M params at
defaults, as in paper Table 1b.  The decode is a deterministic feed-forward
pass: same latent -> bit-identical pixels on a fixed stack.

:class:`VAE` also serves the DC-AE family (:mod:`repro.vae.dcae`): a
:class:`~repro.vae.dcae.DCAEConfig` in :data:`CONFIGS` selects its init and
decode (:func:`_family`).
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.vae import dcae
from repro.vae import layers as L
from repro.vae.dcae import DCAE_F32C32, DCAEConfig


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    name: str = "sd35_vae"
    latent_channels: int = 16
    block_out_channels: Tuple[int, ...] = (128, 256, 512, 512)
    layers_per_block: int = 2            # decoder uses layers_per_block + 1
    groups: int = 32
    scaling_factor: float = 1.5305       # SD3 latent scaling
    shift_factor: float = 0.0609
    image_channels: int = 3
    dtype: Any = jnp.float32

    @property
    def spatial_factor(self) -> int:
        return 2 ** (len(self.block_out_channels) - 1)

    def latent_shape(self, image_hw: int) -> Tuple[int, int, int]:
        s = image_hw // self.spatial_factor
        return (s, s, self.latent_channels)


SD35_VAE = VAEConfig(name="sd35_vae", latent_channels=16)
FLUX_VAE = VAEConfig(name="flux_vae", latent_channels=16,
                     scaling_factor=0.3611, shift_factor=0.1159)
SD15_VAE = VAEConfig(name="sd15_vae", latent_channels=4,
                     scaling_factor=0.18215, shift_factor=0.0)
#: The facade/bench demo stack: tiny but architecturally complete.
DEMO_VAE = VAEConfig(name="demo", latent_channels=4,
                     block_out_channels=(16, 32), layers_per_block=1,
                     groups=4)


#: Decoder configurations by name (the launcher's ``--decoder``): the
#: AutoencoderKL family and DC-AE f32c32 (:mod:`repro.vae.dcae`).
CONFIGS = {c.name: c for c in (SD35_VAE, FLUX_VAE, SD15_VAE, DEMO_VAE,
                               DCAE_F32C32)}


def calibrated_vae(name: str = "demo", seed: int = 0,
                   impl: Optional[str] = None,
                   weight_dtype: str = "float32") -> "VAE":
    """The :class:`VAE` of config ``name`` (:data:`CONFIGS`) with seeded
    random weights and its output range calibrated into the display
    domain (random-init decoders saturate the [-1, 1] clamp, which no
    trained decoder does — and which would make quantization gates and
    fidelity metrics unrepresentative).  Deterministic per seed, so every
    open of the same stack decodes bit-identically."""
    from repro.vae.quantize import calibrate_output_range
    try:
        cfg = CONFIGS[name]
    except KeyError:
        raise ValueError(f"decoder must be one of {sorted(CONFIGS)}: "
                         f"{name!r}") from None
    vae = VAE(cfg, seed=seed, impl=impl)
    calibrate_output_range(vae)
    if weight_dtype != "float32":
        vae.set_weight_dtype(weight_dtype)
    return vae


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def init_decoder(key, cfg: VAEConfig) -> Dict[str, Any]:
    dtype = cfg.dtype
    chs = cfg.block_out_channels
    top = chs[-1]
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "conv_in": L.conv_init(keys[0], 3, 3, cfg.latent_channels, top, dtype),
        "mid": {
            "res1": L.resnet_block_init(keys[1], top, top, dtype),
            "attn": L.attn_block_init(keys[2], top, dtype),
            "res2": L.resnet_block_init(keys[3], top, top, dtype),
        },
        "up": [],
        "norm_out": L.gn_init(chs[0], dtype),
        "conv_out": L.conv_init(keys[4], 3, 3, chs[0], cfg.image_channels, dtype),
    }
    kb = jax.random.split(keys[5], len(chs))
    cin = top
    for i, cout in enumerate(reversed(chs)):        # top -> bottom
        kr = jax.random.split(kb[i], cfg.layers_per_block + 2)
        blocks = []
        for j in range(cfg.layers_per_block + 1):
            blocks.append(L.resnet_block_init(kr[j], cin, cout, dtype))
            cin = cout
        level: Dict[str, Any] = {"blocks": blocks}
        if i < len(chs) - 1:
            level["upsample"] = L.upsample_init(kr[-1], cout, dtype)
        params["up"].append(level)
    return params


def init_encoder(key, cfg: VAEConfig) -> Dict[str, Any]:
    dtype = cfg.dtype
    chs = cfg.block_out_channels
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "conv_in": L.conv_init(keys[0], 3, 3, cfg.image_channels, chs[0], dtype),
        "down": [],
    }
    kb = jax.random.split(keys[1], len(chs))
    cin = chs[0]
    for i, cout in enumerate(chs):
        kr = jax.random.split(kb[i], cfg.layers_per_block + 2)
        blocks = []
        for j in range(cfg.layers_per_block):
            blocks.append(L.resnet_block_init(kr[j], cin, cout, dtype))
            cin = cout
        level: Dict[str, Any] = {"blocks": blocks}
        if i < len(chs) - 1:
            level["downsample"] = L.downsample_init(kr[-1], cout, dtype)
        params["down"].append(level)
    top = chs[-1]
    params["mid"] = {
        "res1": L.resnet_block_init(keys[2], top, top, dtype),
        "attn": L.attn_block_init(keys[3], top, dtype),
        "res2": L.resnet_block_init(keys[4], top, top, dtype),
    }
    params["norm_out"] = L.gn_init(top, dtype)
    params["conv_out"] = L.conv_init(keys[5], 3, 3, top,
                                     2 * cfg.latent_channels, dtype)
    return params


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

_fp32_math = L.fp32_math


def _decode_trunk(params: Dict[str, Any], z: jax.Array, cfg: VAEConfig,
                  impl: Optional[str] = None) -> jax.Array:
    """Shared decode trunk: latent -> pre-epilogue activation [N, 8h, 8w,
    C0] (everything up to, excluding, norm_out + conv_out)."""
    z = z / cfg.scaling_factor + cfg.shift_factor
    x = L.conv2d(z, params["conv_in"], impl=impl)
    x = L.resnet_block(x, params["mid"]["res1"], cfg.groups, impl)
    x = L.attn_block(x, params["mid"]["attn"], cfg.groups, impl)
    x = L.resnet_block(x, params["mid"]["res2"], cfg.groups, impl)
    for level in params["up"]:
        for blk in level["blocks"]:
            x = L.resnet_block(x, blk, cfg.groups, impl)
        if "upsample" in level:
            x = L.upsample(x, level["upsample"], impl=impl)
    return x


@_fp32_math
def decode(params: Dict[str, Any], z: jax.Array, cfg: VAEConfig,
           impl: Optional[str] = None) -> jax.Array:
    """latent [N, h, w, C_lat] -> image [N, 8h, 8w, 3] in [-1, 1]."""
    x = _decode_trunk(params, z, cfg, impl)
    x = L.gn_silu(x, params["norm_out"], groups=cfg.groups, impl=impl)
    return L.conv2d(x, params["conv_out"], impl=impl)


@_fp32_math
def decode_u8(params: Dict[str, Any], z: jax.Array, cfg: VAEConfig,
              impl: Optional[str] = None) -> jax.Array:
    """The uint8 regeneration fast path: latent [N, h, w, C_lat] ->
    displayable uint8 image [N, 8h, 8w, 3].

    Same trunk as :func:`decode`, but the final GN + SiLU + conv_out +
    clamp + quantize runs as one fused epilogue
    (:func:`repro.kernels.ops.output_epilogue`), so the compiled graph's
    last write — and the device->host transfer — is the uint8 image at
    1/4 the float32 bytes."""
    x = _decode_trunk(params, z, cfg, impl)
    from repro.kernels import ops                     # late import (no cycle)
    return ops.output_epilogue(
        x, params["norm_out"]["scale"], params["norm_out"]["bias"],
        params["conv_out"]["w"], params["conv_out"]["b"],
        groups=cfg.groups, impl=impl)


@_fp32_math
def encode(params: Dict[str, Any], x: jax.Array, cfg: VAEConfig,
           impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """image [N, H, W, 3] -> (mean, logvar) latents [N, H/8, W/8, C_lat]."""
    h = L.conv2d(x, params["conv_in"], impl=impl)
    for level in params["down"]:
        for blk in level["blocks"]:
            h = L.resnet_block(h, blk, cfg.groups, impl)
        if "downsample" in level:
            h = L.downsample(h, level["downsample"])
    h = L.resnet_block(h, params["mid"]["res1"], cfg.groups, impl)
    h = L.attn_block(h, params["mid"]["attn"], cfg.groups, impl)
    h = L.resnet_block(h, params["mid"]["res2"], cfg.groups, impl)
    h = L.gn_silu(h, params["norm_out"], groups=cfg.groups, impl=impl)
    moments = L.conv2d(h, params["conv_out"], impl=impl)
    mean, logvar = jnp.split(moments, 2, axis=-1)
    mean = (mean - cfg.shift_factor) * cfg.scaling_factor
    return mean, logvar


def param_count(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def _family(cfg):
    """(init_decoder, init_encoder, decode, decode_u8, encode) of the
    decoder family ``cfg`` belongs to; the encoder entries are None for a
    family whose encoder the program does not carry (DC-AE: objects are
    put as latents)."""
    if isinstance(cfg, DCAEConfig):
        return dcae.init_decoder, None, dcae.decode, dcae.decode_u8, None
    return init_decoder, init_encoder, decode, decode_u8, encode


class VAE:
    """Convenience wrapper bundling config + params + jitted entry points.

    ``weight_dtype`` selects the *storage* precision of the decoder
    weights the uint8 fast path serves from ('float32' | 'bfloat16' |
    'int8', see :mod:`repro.vae.quantize`); the fp32 tree is always kept
    as the oracle — :meth:`decode` and ``decode_u8(z,
    precision='float32')`` run it, which is what the engine's ±1-LSB
    open-time gate compares against.

    ``device`` is where the weights are committed (``None``: JAX's
    default device, uncommitted); :meth:`to_device` makes a view placed
    on another device that shares this instance's jitted entry points.
    """

    def __init__(self, cfg: VAEConfig = SD35_VAE, seed: int = 0,
                 with_encoder: bool = True, impl: Optional[str] = None,
                 weight_dtype: str = "float32"):
        self.cfg = cfg
        self.impl = impl          # None -> chosen from the platform (ops)
        init_dec, init_enc, dec, dec_u8, enc = _family(cfg)
        key = jax.random.PRNGKey(seed)
        kd, ke = jax.random.split(key)
        # one jitted init per tree: on an accelerator, eager init would
        # compile every distinct parameter shape separately
        self.decoder = jax.jit(init_dec, static_argnums=1)(kd, cfg)
        self.encoder = (jax.jit(init_enc, static_argnums=1)(ke, cfg)
                        if with_encoder and init_enc is not None else None)
        self.weight_dtype = "float32"
        self._qparams: Dict[str, Any] = {}
        self.device = None
        self._decode = jax.jit(lambda p, z: dec(p, z, cfg, impl))
        # no input donation: the uint8 output cannot alias the float32
        # latent batch, so XLA reports a donated batch as unusable
        self._decode_u8 = jax.jit(lambda p, z: dec_u8(p, z, cfg, impl))
        self._encode = (None if enc is None else
                        jax.jit(lambda p, x: enc(p, x, cfg, impl)))
        if weight_dtype != "float32":
            self.set_weight_dtype(weight_dtype)

    def set_weight_dtype(self, weight_dtype: str) -> None:
        """(Re-)derive the serving-weight tree at ``weight_dtype`` from
        the current fp32 decoder.  Unconditional: callers that mutated
        ``self.decoder`` (calibration, tests) get fresh quantized params."""
        from repro.vae import quantize as Q       # late import (no cycle)
        self._qparams = {"float32": self.decoder}
        if weight_dtype != "float32":
            self._qparams[weight_dtype] = Q.quantize_decoder(self.decoder,
                                                             weight_dtype)
        self.weight_dtype = weight_dtype

    def _params_for(self, precision: Optional[str]):
        precision = precision or self.weight_dtype
        if not self._qparams:
            self._qparams = {"float32": self.decoder}
        if precision not in self._qparams:
            from repro.vae import quantize as Q
            self._qparams[precision] = Q.quantize_decoder(self.decoder,
                                                          precision)
        return self._qparams[precision]

    def to_device(self, device) -> "VAE":
        """A view of this VAE with every weight tree committed to
        ``device``.  The jitted entry points are shared, so each decode
        runs on the device its inputs live on (one executable per device
        and shape)."""
        out = copy.copy(self)
        out.decoder = jax.device_put(self.decoder, device)
        out.encoder = (None if self.encoder is None
                       else jax.device_put(self.encoder, device))
        out._qparams = {k: (out.decoder if k == "float32"
                            else jax.device_put(v, device))
                        for k, v in self._qparams.items()}
        out.device = device
        return out

    def place(self, x) -> jax.Array:
        """Host or device array -> device array where the weights live."""
        return jax.device_put(x, self.device)

    def decode(self, z: jax.Array) -> jax.Array:
        """Float pixels off the fp32 oracle weights (quantization only
        ever applies to the uint8 serving path)."""
        return self._decode(self.decoder, self.place(z))

    def decode_u8(self, z: jax.Array,
                  precision: Optional[str] = None) -> jax.Array:
        """End-to-end fast path: latents -> uint8 HWC pixels.

        ``precision`` overrides the configured ``weight_dtype`` for this
        call ('float32' forces the oracle weights — the gate's reference
        arm); default serves the configured storage precision."""
        return self._decode_u8(self._params_for(precision), self.place(z))

    def refresh_kernels(self) -> None:
        """Drop compiled decode/encode executables so the next call
        re-traces the kernel dispatch — required for an updated tuning
        cache (:mod:`repro.kernels.autotune`) to take effect, since tuned
        block shapes are baked in at trace time."""
        for name in ("_decode", "_decode_u8", "_encode"):
            clear = getattr(getattr(self, name), "clear_cache", None)
            if clear is not None:
                clear()

    def encode_mean(self, x: jax.Array) -> jax.Array:
        if self.encoder is None:
            raise ValueError(f"decoder {self.cfg.name!r} has no encoder "
                             f"here: put latents, not pixels")
        return self._encode(self.encoder, self.place(x))[0]

    @property
    def decoder_params(self) -> int:
        return param_count(self.decoder)
