"""Image ops for the device-side input pipeline.

The hot path of every blendjax workload is: uint8 frames off the wire →
normalized float (optionally linearized) feeding a conv net.  The reference
does its color conversion per-pixel in numpy on the Blender CPU
(``btb/offscreen.py:105-112``, gamma ``pow`` per frame); blendjax ships
uint8 over the wire (4x less bandwidth than float32) and decodes **on the
TPU**, where XLA fuses the conversion into the first convolution.

Two implementations of the decode:

- :func:`decode_frames` — pure jax.numpy; XLA fuses it; the default.
- :func:`decode_frames_pallas` — a Pallas TPU kernel doing
  uint8→float→(sRGB linearize)→normalize in one VMEM pass; useful when the
  decode feeds multiple consumers and you want it materialized exactly
  once.  Interpret mode follows the one rule in
  :func:`blendjax.ops.flash_attention.resolve_interpret`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from blendjax.ops.flash_attention import resolve_interpret

# sRGB <-> linear (IEC 61966-2-1)


def srgb_to_linear(x):
    """Decode sRGB-encoded [0,1] floats to linear light."""
    return jnp.where(x <= 0.04045, x / 12.92, ((x + 0.055) / 1.055) ** 2.4)


def linear_to_srgb(x):
    """Encode linear-light [0,1] floats to sRGB (what the reference's
    producer-side ``gamma_coeff=2.2`` approximates)."""
    x = jnp.clip(x, 0.0, 1.0)
    return jnp.where(x <= 0.0031308, x * 12.92, 1.055 * x ** (1 / 2.4) - 0.055)


def decode_frames(frames_u8, dtype=jnp.float32, linearize=False, mean=None, std=None):
    """uint8 [0,255] frames -> normalized ``dtype`` in one fused expression.

    Params
    ------
    frames_u8: uint8 array, any shape (typically NHWC).
    dtype: output dtype (use ``jnp.bfloat16`` to feed MXU convs directly).
    linearize: apply sRGB -> linear decode.
    mean/std: optional per-channel normalization (broadcast over trailing
        channel axis).
    """
    x = frames_u8.astype(jnp.float32) * (1.0 / 255.0)
    if linearize:
        x = srgb_to_linear(x)
    if mean is not None:
        x = x - jnp.asarray(mean, jnp.float32)
    if std is not None:
        x = x / jnp.asarray(std, jnp.float32)
    return x.astype(dtype)


# -- Pallas variant ---------------------------------------------------------

_LANE = 128
_SUBLANE = 32  # uint8 min tile is (32, 128)


def _decode_kernel(x_ref, o_ref, *, linearize):
    # Mosaic has no direct uint8->float32 cast (NotImplementedError at
    # lowering; caught by tests/test_tpu_lowering.py) — widen through
    # int32 first, which both legs support
    x = x_ref[:].astype(jnp.int32).astype(jnp.float32) * (1.0 / 255.0)
    if linearize:
        x = jnp.where(x <= 0.04045, x / 12.92, ((x + 0.055) / 1.055) ** 2.4)
    o_ref[:] = x.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dtype", "linearize", "block_rows", "interpret")
)
def decode_frames_pallas(
    frames_u8, dtype=jnp.float32, linearize=False, block_rows=256, interpret=None
):
    """Pallas TPU kernel version of :func:`decode_frames` (no mean/std).

    The frame batch is viewed as a 2-D (rows, 128) array padded to the TPU
    tile grid; each grid step converts ``block_rows`` rows HBM->VMEM->HBM.
    ``interpret=None`` compiles on TPU and interprets elsewhere.
    """
    orig_shape = frames_u8.shape
    total = frames_u8.size
    rows = -(-total // _LANE)  # ceil
    pad_rows = -(-rows // _SUBLANE) * _SUBLANE - rows
    padded = jnp.pad(frames_u8.reshape(-1), (0, (rows + pad_rows) * _LANE - total))
    x2d = padded.reshape(rows + pad_rows, _LANE)

    n_rows = x2d.shape[0]
    block_rows = min(block_rows, n_rows)
    # shrink to a divisor of n_rows that keeps sublane alignment
    while n_rows % block_rows:
        block_rows -= _SUBLANE
    block_rows = max(block_rows, _SUBLANE)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, linearize=linearize),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, dtype),
        grid=(n_rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0)),
        interpret=resolve_interpret(interpret),
        name="decode_frames",
    )(x2d)
    return out.reshape(-1)[:total].reshape(orig_shape)


def normalize(x, mean, std):
    """(x - mean) / std with broadcasting over the channel axis."""
    return (x - jnp.asarray(mean, x.dtype)) / jnp.asarray(std, x.dtype)
