"""State-space mixer (Mamba-1 selective scan) and the gated memory unit.

A state-space layer keeps, per sequence, a **recurrent state** that no
position indexes: ``h`` ``(d_state, d_inner)`` float32 and the last
``d_conv - 1`` inputs of its causal convolution (the *tail*,
``(d_conv - 1, d_inner)``).  Two paths compute the same mixer:

- :func:`mix_sequence` (prefill, the teacher-forced forward): the
  convolution as shifted sums and the recurrence as a **chunked scan**,
  an associative scan inside chunks of :data:`SCAN_CHUNK` positions and
  a ``lax.scan`` carrying ``h`` across them, so that only one chunk's
  ``(chunk, d_state, d_inner)`` terms exist at a time;
- :func:`mix_step` (decode): one position a row, ``h`` and the tail read
  and written whole.

Both start from a state handed in (zeros for a fresh sequence) and hand
the state after their last position back: the caller owns where it
lives (:func:`blendjax.models.seqformer.init_cache`).  Both also return
``y``, the scan's output before the gate, which is the *memory* the
gated memory units of later layers read (:func:`gmu`).

Parameters of a block's ``"ssm"`` entry (channels minor, so that every
leaf and both state tensors are whole lanes on the TPU)::

    in_proj  (d, 2 * d_inner)            [u | z] = x in_proj
    conv_w   (d_conv, d_inner)  conv_b (d_inner,)
    x_proj   (d_inner, dt_rank + 2 * d_state)     [r | B | C] = u x_proj
    dt_w     (dt_rank, d_inner) dt_b (d_inner,)   float32 bias
    a_log    (d_state, d_inner) float32           A = -exp(a_log)
    d_skip   (d_inner,) float32
    out_proj (d_inner, d)

    u   = silu(conv1d_causal(u))          dt = softplus(r dt_w + dt_b)
    h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t
    y_t = h_t . C_t + d_skip u_t          out = (y silu(z)) out_proj

``dt``, ``exp(dt A)``, ``h`` and its update are float32 whatever the
compute dtype; the matrix products run in the compute dtype and
accumulate in float32.  A ``"gmu"`` entry is ``wg (d, d_inner)`` and ``wo
(d_inner, d)``: ``out = (m silu(x wg)) wo``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blendjax.models.layers import scaled_normal

#: positions scanned associatively at a time; ``h`` is carried across
#: chunks.  At 5120 channels x 16 states a chunk's float32 terms are
#: 21 MB each.
SCAN_CHUNK = 64
#: no part of the state is stepped where it lies: the decode step is
#: handed the stepped rows' state, gathered
STEPS_IN_PLACE = 0


def init(key, d_model, d_inner, d_state, d_conv, dt_rank, dtype=jnp.float32):
    """Mamba's published initialisation: ``A = -(1 .. d_state)``, ``D =
    1``, ``dt_b`` the inverse softplus of steps log-uniform in 1e-3 ..
    1e-1, so that states neither die nor blow up over a long sequence."""
    ki, kc, kx, kd, kb, ko = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(kb, (d_inner,), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    return {
        "in_proj": scaled_normal(ki, (d_model, 2 * d_inner), d_model, dtype),
        "conv_w": scaled_normal(kc, (d_conv, d_inner), d_conv, dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "x_proj": scaled_normal(kx, (d_inner, dt_rank + 2 * d_state),
                                d_inner, dtype),
        "dt_w": scaled_normal(kd, (dt_rank, d_inner), dt_rank, dtype),
        "dt_b": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32))[:, None],
            (d_state, d_inner)),
        "d_skip": jnp.ones((d_inner,), jnp.float32),
        "out_proj": scaled_normal(ko, (d_inner, d_model), d_inner, dtype),
    }


def gmu_init(key, d_model, d_inner, dtype=jnp.float32):
    kg, ko = jax.random.split(key)
    return {"wg": scaled_normal(kg, (d_model, d_inner), d_model, dtype),
            "wo": scaled_normal(ko, (d_inner, d_model), d_inner, dtype)}


def state_shapes(p):
    """``(h shape, tail shape)`` of one sequence's recurrent state."""
    d_conv, d_inner = p["conv_w"].shape
    return (p["a_log"].shape[0], d_inner), (d_conv - 1, d_inner)


def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


def _selection(p, u, dtype):
    """``(dt, B, C)`` float32 of conv-activated inputs ``u`` (..., d_inner)."""
    n = p["a_log"].shape[0]
    rank = p["dt_w"].shape[0]
    rbc = _mm(u, p["x_proj"], dtype)
    dt = jax.nn.softplus(_mm(rbc[..., :rank], p["dt_w"], dtype)
                         + p["dt_b"].astype(jnp.float32))
    return dt, rbc[..., rank:rank + n], rbc[..., rank + n:]


def _gate_out(p, y, z, dtype):
    return _mm(y * jax.nn.silu(z), p["out_proj"], dtype).astype(dtype)


def conv_sequence(w, b, fresh, tail, dtype):
    """The depthwise causal convolution of ``taps`` and its SiLU over a
    sequence: weights ``w`` (taps, C), bias ``b`` (C,), inputs ``fresh``
    (B, T, C) in ``dtype`` after the ``tail`` (B, taps - 1, C) of the
    inputs before them -> ``(silu(out) (B, T, C) float32, the tail after
    T)``.  The sums are float32."""
    t = fresh.shape[1]
    padded = jnp.concatenate([tail.astype(dtype), fresh], axis=1)
    w = w.astype(jnp.float32)
    u = b.astype(jnp.float32) + sum(
        padded[:, k:k + t].astype(jnp.float32) * w[k]
        for k in range(w.shape[0]))
    return jax.nn.silu(u), padded[:, t:].astype(tail.dtype)


def conv_step(w, b, fresh, tail, dtype):
    """:func:`conv_sequence` at one position a row: ``fresh`` (B, C) ->
    ``(silu(out) (B, C) float32, the new tail)``."""
    taps = jnp.concatenate([tail.astype(dtype), fresh[:, None]], axis=1)
    u = jax.nn.silu(
        b.astype(jnp.float32)
        + jnp.sum(taps.astype(jnp.float32) * w.astype(jnp.float32), axis=1))
    return u, taps[:, 1:].astype(tail.dtype)


def _combine(lo, hi):
    """``h -> a h + b`` composed: first ``lo``, then ``hi``."""
    return hi[0] * lo[0], hi[0] * lo[1] + hi[1]


def mix_sequence(p, x, h, tail, dtype):
    """Normed input ``x`` (B, T, d) from the state ``h`` (B, d_state,
    d_inner) float32 and ``tail`` (B, d_conv - 1, d_inner) ->
    ``(out (B, T, d), y (B, T, d_inner) float32, h after T, tail after
    T)``."""
    b, t, _ = x.shape
    d_inner = p["conv_w"].shape[1]
    uz = _mm(x, p["in_proj"], dtype)
    u_in, z = uz[..., :d_inner].astype(dtype), uz[..., d_inner:]
    with jax.named_scope("conv"):
        u, new_tail = conv_sequence(p["conv_w"], p["conv_b"], u_in, tail,
                                    dtype)
    with jax.named_scope("scan"):
        dt, b_sel, c_sel = _selection(p, u, dtype)
        a = -jnp.exp(p["a_log"].astype(jnp.float32))  # (n, d_inner)
        chunk = min(SCAN_CHUNK, t)
        pad = -t % chunk
        # a step of dt 0 leaves the state as it is: the padding of the
        # last chunk
        parts = [jnp.pad(v, ((0, 0), (0, pad), (0, 0))).reshape(
            b, -1, chunk, v.shape[-1]).swapaxes(0, 1)
            for v in (dt, dt * u, b_sel, c_sel)]

        def one_chunk(h, part):
            dt_c, dtu_c, b_c, c_c = part  # (B, chunk, ...)
            decay = jnp.exp(dt_c[:, :, None, :] * a)
            drive = dtu_c[:, :, None, :] * b_c[..., None]
            acc_a, acc_b = lax.associative_scan(_combine, (decay, drive),
                                                axis=1)
            hs = acc_a * h[:, None] + acc_b  # (B, chunk, n, d_inner)
            return hs[:, -1], jnp.einsum("bcnd,bcn->bcd", hs, c_c)

        h, ys = lax.scan(one_chunk, h.astype(jnp.float32), parts)
        y = ys.swapaxes(0, 1).reshape(b, -1, d_inner)[:, :t]
        y = y + p["d_skip"].astype(jnp.float32) * u
    return _gate_out(p, y, z, dtype), y, h, new_tail


def mix_step(p, x, h, tail, dtype):
    """One position a row: ``x`` (B, d), ``h`` (B, d_state, d_inner)
    float32, ``tail`` (B, d_conv - 1, d_inner) -> ``(out (B, d), y (B,
    d_inner) float32, new h, new tail)``; :func:`mix_sequence` at T = 1
    without its scan."""
    d_inner = p["conv_w"].shape[1]
    uz = _mm(x, p["in_proj"], dtype)
    u_in, z = uz[..., :d_inner].astype(dtype), uz[..., d_inner:]
    with jax.named_scope("conv"):
        u, new_tail = conv_step(p["conv_w"], p["conv_b"], u_in, tail, dtype)
    with jax.named_scope("update"):
        dt, b_sel, c_sel = _selection(p, u, dtype)
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        h = (jnp.exp(dt[:, None, :] * a) * h.astype(jnp.float32)
             + (dt * u)[:, None, :] * b_sel[..., None])
        y = jnp.einsum("bnd,bn->bd", h, c_sel)
        y = y + p["d_skip"].astype(jnp.float32) * u
    return _gate_out(p, y, z, dtype), y, h, new_tail


def gmu(p, x, memory, dtype):
    """Gated memory unit: the memory ``y`` of the last state-space layer
    (the same positions as ``x``), gated by this layer's own input."""
    gate = jax.nn.silu(_mm(x, p["wg"], dtype))
    return _mm(memory * gate, p["wo"], dtype).astype(dtype)
