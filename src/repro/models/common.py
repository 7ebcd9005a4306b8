"""Shared model components: norms, rope, activations, initializers, taps."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def param_dtype(cfg) -> jnp.dtype:
    # params stored in the compute dtype (bf16 at scale); norm scales fp32
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initializers (all pure; usable under jax.eval_shape)
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
    if scale is None:
        scale = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(key, cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_norm(p, x, cfg):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


def rms_head_norm(x, scale, eps):
    """QK-norm over the last (head) dim."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> np.ndarray:
    """Per-pair inverse frequencies; dim must be even."""
    return 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))


def apply_rope(x, positions, theta: float, inv=None, gain: float = 1.0):
    """x: (..., T, H, D) with D even, positions: (..., T) int32. Pairs
    (2i, 2i+1) turn at ``inv[i]`` (default ``rope_freqs(D, theta)``); the
    result is scaled by ``gain`` (YaRN's cos/sin factor)."""
    d = x.shape[-1]
    if inv is None:
        inv = rope_freqs(d, theta)
    inv = jnp.asarray(inv, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., T, D/2)
    cos = jnp.cos(ang)[..., None, :] * gain
    sin = jnp.sin(ang)[..., None, :] * gain
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor for a context ``factor`` times longer."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs) -> np.ndarray:
    """YaRN's per-pair inverse frequencies (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): pairs that turn fewer than
    ``beta_slow`` times over the original context are interpolated
    (frequency / ``factor``), those that turn more than ``beta_fast``
    times are kept, and a linear ramp blends the pairs between."""
    def corr_dim(rot):
        return dim * math.log(rs.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr_dim(rs.beta_fast)), 0)
    high = min(math.ceil(corr_dim(rs.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = rope_freqs(dim, theta)
    kept = 1.0 - ramp
    return extra / rs.factor * (1.0 - kept) + extra * kept


def yarn_gains(rs) -> tuple:
    """(rope gain on cos/sin, factor on the softmax scale) of a YaRN
    block: mscale(factor, mscale) / mscale(factor, mscale_all_dim), and
    mscale(factor, mscale_all_dim)^2 where ``mscale_all_dim`` is set."""
    f = rs.factor
    gain = yarn_mscale(f, rs.mscale) / yarn_mscale(f, rs.mscale_all_dim)
    soft = yarn_mscale(f, rs.mscale_all_dim) ** 2 if rs.mscale_all_dim \
        else 1.0
    return gain, soft


# ---------------------------------------------------------------------------
# tap (activation tape) utilities
# ---------------------------------------------------------------------------

_TAP_DTYPE = jnp.float32


class tap_dtype:
    """Trace-time context setting the dtype activation taps are emitted in.

    fp32 (default) maximises statistics fidelity; bf16 halves the
    calibration pass's activation HBM traffic end-to-end — the gram kernel
    streams bf16 tiles and still accumulates fp32 in VMEM (the
    ``stats_dtype`` knob of ``repro.core.calibrate.CalibrationEngine``
    wraps the model forward in this context). A Python-level knob: it must
    be active while the forward is *traced*, which the engine guarantees by
    entering it inside its jitted reduce function.
    """

    def __init__(self, dtype):
        self.dtype = jnp.dtype(dtype)

    def __enter__(self):
        global _TAP_DTYPE
        self._prev, _TAP_DTYPE = _TAP_DTYPE, self.dtype
        return self

    def __exit__(self, *exc):
        global _TAP_DTYPE
        _TAP_DTYPE = self._prev
        return False


def tap(taps: dict | None, name: str, value):
    """Record an intermediate activation for CORP calibration.

    ``taps`` is None when not taping (no memory cost). Values are stored in
    the active ``tap_dtype`` — fp32 by default; every statistics reduction
    downstream accumulates in fp32 regardless of the tape dtype.
    """
    if taps is not None:
        taps[name] = value.astype(_TAP_DTYPE)


def merge_taps(dst: dict | None, src: dict, prefix: str):
    if dst is not None:
        for k, v in src.items():
            dst[f"{prefix}/{k}" if prefix else k] = v
