"""Mamba-2 state-space mixer (the SSD layer, arXiv:2405.21060): a
**matrix state a head** with a scalar decay a head.

A Mamba-2 layer keeps, per sequence, a recurrent state that no position
indexes: ``S`` ``(H, P, N)`` float32 (``P`` a head's width, ``N`` the
state size) and the last ``taps - 1`` inputs of its causal convolution
over ``x | B | C`` (the *tail*).  With ``x`` the normed input, one group
of ``B`` and ``C`` shared by every head::

    [z | xBC | dt] = x W_in              widths d_inner | d_inner + 2N | H
    xBC   = silu(conv_taps(xBC) + b_conv)           [x | B | C] = xBC
    dt    = softplus(dt + dt_bias)      A = -exp(A_log)        (H,) each
    S_h  <- exp(dt_h A_h) S_h + dt_h x_h B^T        (x_h: head h's P of x)
    y_h   = S_h C + D_h x_h
    out   = (RMSNorm_d_inner(y * silu(z)) w_norm) W_out

Two paths compute the same mixer:

- :func:`mix_sequence` (prefill, the teacher-forced forward) in the
  chunked (SSD) form, :class:`SsdSpec`'s ``chunk`` positions at a time.
  Inside a chunk, with ``gamma`` the running sum of ``dt A`` and ``L_ts =
  exp(gamma_t - gamma_s)`` (``s <= t``, else 0), the reads are ``(L * C
  B^T)(dt x)`` plus the carried state's ``exp(gamma_t) S C_t``, and the
  state after the chunk ``exp(gamma_C) S + sum_s exp(gamma_C - gamma_s)
  dt_s x_s B_s^T``.  Everything that does not hold ``S`` is computed for
  all chunks at once; a ``lax.scan`` carries ``S`` across them.
- :func:`mix_step` (decode): one position a row, the tail read and
  written whole, ``S`` stepped where it lies in the pool by the delta-free
  variant of :func:`blendjax.ops.gdn_update.gdn_update` (``u = dt x``,
  ``k = B``, ``q = C``, ``alpha = exp(dt A)``).

Both go on from a state handed in (zeros for a fresh sequence) and hand
back the state after their last position; :func:`mix_step` is handed the
pool's whole leaf and the rows it steps (:data:`STEPS_IN_PLACE`).

Parameters of a block's ``"ssd"`` entry (no biases but the
convolution's)::

    w_in (d, 2 d_inner + 2N + H)    conv_w (taps, d_inner + 2N)  conv_b
    dt_bias, a_log, d_skip (H,) float32    norm {"scale": (d_inner,)}
    w_out (d_inner, d)    spec: SsdSpec (static)

The products of the projections run in the compute dtype and accumulate
in float32.  Float32 whatever the compute dtype: the convolution's sums,
``dt``, the decays and their exponentials, ``S``, every product that
updates or reads it (at ``Precision.HIGHEST``, as in
:mod:`blendjax.models.deltanet`), and the gated norm.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blendjax.models.layers import rms_norm, scaled_normal
from blendjax.models.mamba import conv_sequence, conv_step
from blendjax.ops import gdn_update

_HIGHEST = lax.Precision.HIGHEST
#: how many leading parts of the state (``state_shapes``' order) the
#: decode step takes as the pool's whole leaf, with the rows it steps
STEPS_IN_PLACE = 1


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SsdSpec:
    """What the shapes of a Mamba-2 block do not say: the prefill's chunk
    and the gated norm's epsilon."""

    chunk: int = 256
    norm_eps: float = 1e-5


def init(key, d_model, n_heads, head_dim, d_state, taps, dtype=jnp.float32):
    """The published Mamba-2 initialisation: ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of steps log-uniform in 1e-3 ..
    1e-1, ``D = 1``; projections normal at ``fan_in ** -0.5``."""
    ki, kc, kb, ko, kl, kd = jax.random.split(key, 6)
    d_inner = n_heads * head_dim
    conv = d_inner + 2 * d_state
    dt = jnp.exp(jax.random.uniform(kd, (n_heads,), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    return {
        "w_in": scaled_normal(ki, (d_model, 2 * d_inner + 2 * d_state
                                   + n_heads), d_model, dtype),
        "conv_w": scaled_normal(kc, (taps, conv), taps, dtype),
        "conv_b": (0.1 * jax.random.normal(kb, (conv,))).astype(dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(kl, (n_heads,), minval=1.0,
                                            maxval=16.0)),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm": {"scale": jnp.ones((d_inner,), dtype)},
        "w_out": scaled_normal(ko, (d_inner, d_model), d_inner, dtype),
    }


def dims(p):
    """``(H, P, N, taps)`` of a block's ``"ssd"`` entry."""
    h = p["a_log"].shape[0]
    d_inner = p["w_out"].shape[0]
    taps, conv = p["conv_w"].shape
    return h, d_inner // h, (conv - d_inner) // 2, taps


def state_shapes(p):
    """One sequence's recurrent state: ``S`` ``(H, P, N)`` and the tail
    ``(taps - 1, d_inner + 2N)``."""
    h, pw, n, taps = dims(p)
    return (h, pw, n), (taps - 1, h * pw + 2 * n)


def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _split(p, x, dtype):
    """``x`` (..., d) -> ``(z, xBC in dtype, dt float32 (..., H))``."""
    h, pw, n, _ = dims(p)
    d_inner = h * pw
    zxbcdt = _mm(x, p["w_in"], dtype)
    dt = jax.nn.softplus(zxbcdt[..., -h:] + p["dt_bias"].astype(jnp.float32))
    return (zxbcdt[..., :d_inner],
            zxbcdt[..., d_inner:2 * d_inner + 2 * n].astype(dtype), dt)


def _parts(p, xbc):
    """The convolved ``xBC`` (..., d_inner + 2N) -> ``(x (..., H, P), B,
    C (..., N))``."""
    h, pw, n, _ = dims(p)
    return (xbc[..., :h * pw].reshape(*xbc.shape[:-1], h, pw),
            xbc[..., h * pw:h * pw + n], xbc[..., h * pw + n:])


def decay(p, dt):
    """``exp(dt A)`` (..., H): how much of the state a step keeps."""
    return jnp.exp(dt * -jnp.exp(p["a_log"].astype(jnp.float32)))


def _gate_out(p, y, x, z, dtype):
    """Reads ``y`` (..., H, P) float32 and the head's input ``x`` -> (...,
    d): the skip, the gated norm and the output projection."""
    y = y + p["d_skip"].astype(jnp.float32)[:, None] * x
    y = y.reshape(z.shape) * jax.nn.silu(z)
    with jax.named_scope("norm"):
        y = rms_norm(p["norm"]["scale"], y, p["spec"].norm_eps)
    return _mm(y, p["w_out"], dtype).astype(dtype)


def chunked_scan(x, dt, a, b, c, state, chunk):
    """The recurrence over a sequence, ``chunk`` positions at a time:
    ``x`` (B, T, H, P), ``dt`` (B, T, H), ``a`` (H,) (``A``, negative),
    ``b, c`` (B, T, N), ``state`` (B, H, P, N), all float32 -> ``(y (B,
    T, H, P), state after T)``, ``y`` without the skip.  A padded position
    (``dt = 0``) leaves the state as it is."""
    bsz, t, h, pw = x.shape
    size = min(chunk, t)
    pad = -t % size
    n = (t + pad) // size

    def parts(v):  # (B, T, ...) -> (B, n, size, ...)
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(bsz, n, size, *v.shape[2:])

    x, dt, b, c = (parts(v) for v in (x, dt, b, c))
    gamma = jnp.cumsum(dt * a, axis=2)                  # (B, n, size, H)
    at = jnp.arange(size)
    by_head = jnp.moveaxis(gamma, 3, 2)                 # (B, n, H, size)
    # the decay from position s to position t >= s; 0 above the diagonal
    within = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                               by_head[..., :, None] - by_head[..., None, :],
                               -jnp.inf))
    written = x * dt[..., None]                         # dt x (.., H, P)
    scores = _dot("bntk,bnsk->bnts", c, b)
    y = _dot("bnhts,bnshp->bnthp", within * scores[:, :, None], written)
    last = gamma[:, :, -1]                              # (B, n, H)
    carried = written * jnp.exp(last[:, :, None] - gamma)[..., None]
    drive = _dot("bnshp,bnsk->bnhpk", carried, b)       # (B, n, H, P, N)

    def one_chunk(s, part):
        c_c, gamma_c, drive_c, last_c = part
        read = _dot("bhpk,btk->bthp", s, c_c) * jnp.exp(gamma_c)[..., None]
        return jnp.exp(last_c)[..., None, None] * s + drive_c, read

    state, reads = lax.scan(
        one_chunk, state.astype(jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (c, gamma, drive, last)))
    y = y + jnp.moveaxis(reads, 0, 1)
    return y.reshape(bsz, n * size, h, pw)[:, :t], state


def mix_sequence(p, x, state, tail, dtype):
    """Normed input ``x`` (B, T, d) from ``state`` (B, H, P, N) float32
    and the tail (B, taps - 1, d_inner + 2N) -> ``(out (B, T, d), state
    after T, tail after T)``."""
    z, xbc, dt = _split(p, x, dtype)
    with jax.named_scope("conv"):
        xbc, tail = conv_sequence(p["conv_w"], p["conv_b"], xbc, tail, dtype)
    xs, b, c = _parts(p, xbc)
    with jax.named_scope("chunk"):
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        y, state = chunked_scan(xs, dt, a, b, c, state, p["spec"].chunk)
    return _gate_out(p, y, xs, z, dtype), state, tail


def mix_step(p, x, pool, tail, dtype, rows=None):
    """One position a row: ``x`` (B, d), ``pool`` a cache's whole state
    leaf ``(S, ..., P, N)`` float32 (the heads on the axes between) of
    which ``rows`` (B,) are stepped (by default its first B), the tail (B,
    taps - 1, d_inner + 2N) -> ``(out (B, d), the pool with the rows' new
    state, the new tail)``; :func:`mix_sequence` at T = 1 without its
    chunks.  The state is stepped where it lies, each row's read once and
    written back in place (:func:`blendjax.ops.gdn_update.gdn_update`
    without its delta term; the read is taken from the old state, ``S_t C
    = alpha S C + dt x (B . C)``)."""
    z, xbc, dt = _split(p, x, dtype)
    with jax.named_scope("conv"):
        xbc, tail = conv_step(p["conv_w"], p["conv_b"], xbc, tail, dtype)
    xs, b, c = _parts(p, xbc)
    if rows is None:
        rows = jnp.arange(x.shape[0])
    with jax.named_scope("update"):
        y, pool = gdn_update.gdn_update(pool, rows, c, b, xs, decay(p, dt),
                                        dt, delta=False)
    return _gate_out(p, y, xs, z, dtype), pool, tail
