"""Minimal functional NN layers (pure jax pytrees).

blendjax models are plain ``{name: array}`` pytrees with ``init``/``apply``
functions — no module framework — so they jit, shard (NamedSharding over
pytree leaves), and donate cleanly.  Convs are NHWC/HWIO, the TPU-native
layout; compute dtype is a parameter so models run bfloat16 on the MXU with
float32 params.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def conv_init(key, in_ch, out_ch, ksize=3):
    """He-normal conv kernel (HWIO) + zero bias."""
    fan_in = ksize * ksize * in_ch
    w = jax.random.normal(key, (ksize, ksize, in_ch, out_ch)) * jnp.sqrt(2.0 / fan_in)
    return {"w": w, "b": jnp.zeros((out_ch,))}


def conv_apply(p, x, stride=1, padding="SAME", dtype=None):
    dtype = dtype or x.dtype
    out = lax.conv_general_dilated(
        x.astype(dtype),
        p["w"].astype(dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out + p["b"].astype(dtype)


def dense_init(key, d_in, d_out):
    w = jax.random.normal(key, (d_in, d_out)) * jnp.sqrt(2.0 / d_in)
    return {"w": w, "b": jnp.zeros((d_out,))}


def scaled_normal(key, shape, fan_in, dtype=jnp.float32):
    """Normal weights with standard deviation ``fan_in ** -0.5``."""
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)


def dense_apply(p, x, dtype=None):
    dtype = dtype or x.dtype
    return x.astype(dtype) @ p["w"].astype(dtype) + p["b"].astype(dtype)


def gelu(x):
    return jax.nn.gelu(x)


def rms_norm(scale, x, eps=1e-6):
    """``x * rsqrt(mean(x^2) + eps) * scale``, computed in float32."""
    x32 = x.astype(jnp.float32)
    out = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention-magnitude correction ``0.1 * mscale * ln(factor)
    + 1`` (1 at ``factor <= 1``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dh, base, factor, beta_fast, beta_slow, original_max):
    """YaRN inverse frequencies (``deepseek_yarn``) over ``dh // 2`` rope
    pairs: ``base^(-2i/dh)`` below the correction dimension of
    ``beta_fast`` rotations over ``original_max`` positions, that over
    ``factor`` above the one of ``beta_slow``, the linear ramp between.
    The same for Hugging Face's ``rope_type: yarn`` at its default
    ``truncate: true``: the two correction dimensions are floored and
    ceiled to whole pairs and clipped to ``[0, dh - 1]``, so the ramp runs
    between whole pair indices (at ``dh`` 128, ``theta`` 500000 and 8192
    original positions, from pair 18 to pair 35)."""
    def correction_dim(rotations):
        return dh * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dh - 1)
    half = dh // 2
    plain = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def rope_table(positions, dh, base=10000.0, yarn=None, attention_factor=None):
    """Rotary-embedding cos/sin tables for ``positions`` (any traced or
    static int array) at per-head dim ``dh`` (even).  f32: the rotation
    is applied in f32 and cast back by :func:`apply_rope`.

    ``yarn`` (``factor, beta_fast, beta_slow, original_max``, then for
    ``deepseek_yarn`` ``mscale, mscale_all_dim``) scales the frequencies
    as :func:`yarn_inv_freq` does and the tables by ``attention_factor``
    where it is given (Hugging Face's ``rope_type: yarn``), else by
    ``m(mscale) / m(mscale_all_dim)``.

    Precision bound: the highest-frequency angle equals the raw
    position, and f32's ulp at position p is ~p * 6e-8 radians — sub-
    milliradian phase error through ~1e4, ~1e-2 rad at 1e5-1e6, and
    meaningless past 2^24 (adjacent positions collide).  Practical
    horizon ~1e5-1e6 positions; a reduced-angle scheme would be needed
    beyond that."""
    half = dh // 2
    if yarn is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[:, None] * freqs[None]
        return jnp.cos(ang), jnp.sin(ang)
    factor, beta_fast, beta_slow, original_max, *mscales = yarn
    freqs = yarn_inv_freq(dh, base, factor, beta_fast, beta_slow,
                          original_max)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None]
    m = attention_factor
    if m is None:
        m = yarn_mscale(factor, mscales[0]) / yarn_mscale(factor, mscales[1])
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin):
    """Rotate (B, T, H, Dh) (or (B, H, Dh) single-position) q/k by the
    tables from :func:`rope_table`.  Rotation by absolute position makes
    q·k depend only on the RELATIVE offset — the property that unties
    sequence length from any learned table."""
    single = x.ndim == 3
    if single:
        x = x[:, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    out = out.astype(x.dtype)
    return out[:, 0] if single else out


def apply_rope_rows(x, cos, sin):
    """Rotate a single-position (B, H, Dh) q/k where each batch row sits
    at its OWN position: ``cos``/``sin`` are (B, Dh/2) tables from
    :func:`rope_table` over a (B,) position vector.  The per-row decode
    path of :func:`blendjax.models.seqformer.decode_step` (policy
    serving: one batched step over episodes at heterogeneous timesteps)
    uses this; :func:`apply_rope` covers the batch-uniform case."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[:, None, :]
    s = sin[:, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)
