"""Gated delta-rule linear attention (Gated DeltaNet, arXiv:2412.06464):
a **matrix state a head**.

A linear-attention layer keeps, per sequence and head, a recurrent state
``S`` ``(dv, dk)`` float32 that no position indexes, and the last ``taps -
1`` inputs of each of its three causal convolutions (the *tails* of the
query, key and value streams).  Per head, with ``x`` the normed input::

    q~ = x wq    k~ = x wk    v~ = x wv       each through a depthwise causal
                                              convolution of `taps` and SiLU
    q = l2norm(q~) / sqrt(dk)    k = l2norm(k~)
    beta  = sigmoid(x wb)  (x 2 under `allow_neg_eigval`: beta in (0, 2), so
                            I - beta k k^T has an eigenvalue in (-1, 1))
    g     = -exp(a_log) softplus(x wa + dt_bias)        alpha = exp(g)
    S'    = alpha S_{t-1}        u = beta (v - S' k)
    S_t   = S' + u k^T           o = S_t q
    out   = (RMSNorm_dv(o) * silu(x wg)) wo

Two paths compute the same mixer:

- :func:`mix_sequence` (prefill, the teacher-forced forward) in chunks of
  :data:`CHUNK` positions.  Inside a chunk the WY form: with ``gamma`` the
  running sum of ``g`` and ``Gamma_im = exp(gamma_i - gamma_m)`` the decay
  between two positions, ``A = (I + strict_tril(diag(beta) (K K^T *
  Gamma)))^-1 diag(beta)`` (one triangular solve), ``U = A V``, ``W = A (K
  * exp(gamma))``; from the state ``S`` before the chunk the updates are
  ``U - W S^T``, the reads ``(Q * exp(gamma)) S^T + tril(Q K^T * Gamma)
  (U - W S^T)`` and the state after it ``exp(gamma_C) S + (U - W S^T)^T (K
  * exp(gamma_C - gamma))``.  Everything that does not hold ``S`` is
  computed for all chunks at once; a ``lax.scan`` carries ``S`` across
  them.
- :func:`mix_step` (decode): one position a row, the tails read and
  written whole, ``S`` stepped where it lies in the pool by one kernel
  (:mod:`blendjax.ops.gdn_update`).

Both go on from a state handed in (zeros for a fresh sequence) and hand
back the state after their last position: the caller owns where it lives
(:func:`blendjax.models.seqformer.init_cache`); :func:`mix_step` is handed
the pool's whole leaf and the rows it steps (:data:`STEPS_IN_PLACE`).

Parameters of a block's ``"gdn"`` entry (no biases)::

    wq, wk (d, H dk)    wv, wg (d, H dv)    wo (H dv, d)    wa, wb (d, H)
    conv_q, conv_k (taps, H dk)    conv_v (taps, H dv)
    a_log, dt_bias (H,) float32    o_norm {"scale": (dv,)}
    spec: GdnSpec (static)

The products of the projections run in the compute dtype and accumulate
in float32.  Float32 whatever the compute dtype: the convolutions' sums,
both l2 norms, the gates and their exponentials, ``S``, every product that
updates or reads it (at ``Precision.HIGHEST``: on a TPU a float32 product
is otherwise rounded to bfloat16 on its way in; the decode kernel's are
element by element on the vector unit), and the output norm.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blendjax.models.layers import rms_norm, scaled_normal
from blendjax.ops import gdn_update

#: positions solved together; ``S`` is carried across chunks
CHUNK = 64
L2_EPS = 1e-6
_HIGHEST = lax.Precision.HIGHEST

#: the three convolved streams, in the order of their tails
STREAMS = ("q", "k", "v")
#: how many leading parts of the state (``state_shapes``' order) the
#: decode step takes as the pool's whole leaf, with the rows it steps
STEPS_IN_PLACE = 1


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class GdnSpec:
    """What the shapes of a linear-attention block do not say."""

    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6


def init(key, d_model, n_heads, key_dim, value_dim, taps, spec=GdnSpec(),
         dtype=jnp.float32):
    """The published layer's initialisation: ``a_log = log U(0, 16)`` and
    ``dt_bias`` the inverse softplus of steps log-uniform in 1e-3 .. 1e-1,
    so that ``alpha`` spans about 0.2 .. 0.999 and a state neither dies
    nor saturates."""
    kq, kk, kv, kg, ko, ka, kb, kc, kl, kd = jax.random.split(key, 10)
    wide_k, wide_v = n_heads * key_dim, n_heads * value_dim
    dt = jnp.exp(jax.random.uniform(kd, (n_heads,), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    convs = jax.random.split(kc, 3)
    return {
        "wq": scaled_normal(kq, (d_model, wide_k), d_model, dtype),
        "wk": scaled_normal(kk, (d_model, wide_k), d_model, dtype),
        "wv": scaled_normal(kv, (d_model, wide_v), d_model, dtype),
        "wg": scaled_normal(kg, (d_model, wide_v), d_model, dtype),
        "wo": scaled_normal(ko, (wide_v, d_model), wide_v, dtype),
        "wa": scaled_normal(ka, (d_model, n_heads), d_model, dtype),
        "wb": scaled_normal(kb, (d_model, n_heads), d_model, dtype),
        **{"conv_" + s: (0.5 * jax.random.normal(k, (taps, width))
                         ).astype(dtype)
           for s, k, width in zip(STREAMS, convs, (wide_k, wide_k, wide_v))},
        "a_log": jnp.log(jax.random.uniform(kl, (n_heads,), minval=1e-3,
                                            maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": {"scale": jnp.ones((value_dim,), dtype)},
        "spec": spec,
    }


def dims(p):
    """``(H, dk, dv, taps)`` of a block's ``"gdn"`` entry."""
    h = p["a_log"].shape[0]
    return (h, p["wq"].shape[1] // h, p["wv"].shape[1] // h,
            p["conv_q"].shape[0])


def state_shapes(p):
    """One sequence's recurrent state: ``S`` ``(H, dv, dk)`` and the three
    tails ``(taps - 1, width)``, in :data:`STREAMS`' order."""
    h, dk, dv, taps = dims(p)
    return ((h, dv, dk), (taps - 1, h * dk), (taps - 1, h * dk),
            (taps - 1, h * dv))


def _mm(x, w, dtype):
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _streams(p, x, tails, dtype):
    """``x`` (B, T, d) and the three tails (B, taps - 1, width) ->
    ``(q (B, T, H, dk), k, v (B, T, H, dv), new tails)``, float32."""
    h, dk, _, taps = dims(p)
    t = x.shape[1]
    outs, new_tails = [], []
    with jax.named_scope("conv"):
        for s, tail in zip(STREAMS, tails):
            fresh = _mm(x, p["w" + s], dtype).astype(dtype)
            padded = jnp.concatenate([tail.astype(dtype), fresh], axis=1)
            w = p["conv_" + s].astype(jnp.float32)
            mixed = sum(padded[:, i:i + t].astype(jnp.float32) * w[i]
                        for i in range(taps))
            outs.append(jax.nn.silu(mixed).reshape(*mixed.shape[:2], h, -1))
            new_tails.append(padded[:, t:].astype(tail.dtype))
    q, k, v = outs
    return _l2norm(q) * dk ** -0.5, _l2norm(k), v, new_tails


def gates(p, x, dtype):
    """``(g, beta)`` (..., H) float32: the log of the decay and the
    writing strength."""
    with jax.named_scope("gate"):
        g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            _mm(x, p["wa"], dtype) + p["dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(_mm(x, p["wb"], dtype))
        return g, (2.0 * beta if p["spec"].allow_neg_eigval else beta)


def _gate_out(p, o, x, dtype):
    """Reads ``o`` (..., H, dv) float32 -> (..., d): the gated norm and
    the output projection."""
    o = rms_norm(p["o_norm"]["scale"], o, p["spec"].norm_eps)
    gate = jax.nn.silu(_mm(x, p["wg"], dtype))
    return _mm(o.reshape(gate.shape) * gate, p["wo"], dtype).astype(dtype)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def chunked_rule(q, k, v, g, beta, state):
    """The recurrence over a sequence, :data:`CHUNK` positions at a time:
    ``q, k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g, beta`` (B, T, H),
    ``state`` (B, H, dv, dk), all float32 -> ``(o (B, T, H, dv), state
    after T)``.  A padded position (``g = 0``, everything else 0) leaves
    the state as it is."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(CHUNK, t)
    pad = -t % c
    n = (t + pad) // c

    def parts(a):  # (B, T, H, ...) -> (N, B, H, C, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, n, c, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (parts(a) for a in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                      # (N, B, H, C)
    at = jnp.arange(c)
    # the decay from position m to position i >= m; 0 above the diagonal
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))
    system = jnp.eye(c) + jnp.where(
        at[:, None] > at[None, :],
        beta[..., None] * _dot("nbhik,nbhmk->nbhim", k, k) * decay, 0.0)
    grown = jnp.exp(gamma)[..., None]
    solved = lax.linalg.triangular_solve(
        system, beta[..., None] * jnp.concatenate([v, k * grown], -1),
        left_side=True, lower=True, unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]
    reads = _dot("nbhik,nbhmk->nbhim", q, k) * decay
    last = gamma[..., -1:]
    carried = k * jnp.exp(last - gamma)[..., None]
    kept = jnp.exp(last)[..., None]                     # (N, B, H, 1, 1)

    def one_chunk(s, part):
        u0_c, w_c, q_c, reads_c, carried_c, kept_c = part
        u = u0_c - _dot("bhck,bhvk->bhcv", w_c, s)
        o = _dot("bhck,bhvk->bhcv", q_c, s) + _dot("bhim,bhmv->bhiv",
                                                    reads_c, u)
        return kept_c * s + _dot("bhcv,bhck->bhvk", u, carried_c), o

    state, o = lax.scan(one_chunk, state.astype(jnp.float32),
                        (u0, w, q * grown, reads, carried, kept))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)       # (B, N, C, H, dv)
    return o.reshape(b, n * c, h, dv)[:, :t], state


def mix_sequence(p, x, state, tail_q, tail_k, tail_v, dtype):
    """Normed input ``x`` (B, T, d) from ``state`` (B, H, dv, dk) float32
    and the three tails (B, taps - 1, width) -> ``(out (B, T, d), state
    after T, the three tails after T)``."""
    q, k, v, tails = _streams(p, x, (tail_q, tail_k, tail_v), dtype)
    g, beta = gates(p, x, dtype)
    with jax.named_scope("chunk"):
        o, state = chunked_rule(q, k, v, g, beta, state)
    return (_gate_out(p, o, x, dtype), state, *tails)


def mix_step(p, x, pool, tail_q, tail_k, tail_v, dtype, rows=None):
    """One position a row: ``x`` (B, d), ``pool`` a cache's whole state
    leaf ``(S, ..., dv, dk)`` float32 (the heads on the axes between) of
    which ``rows`` (B,) are stepped (by default its first B), the tails
    (B, taps - 1, width) -> ``(out (B, d), the pool with the rows' new
    state, the three new tails)``; :func:`mix_sequence` at T = 1 without
    its solve.  The state is stepped where it lies, by
    :func:`blendjax.ops.gdn_update.gdn_update`: each row's read once, for
    both products and its update (the read is taken from the old state,
    ``S_t q = alpha S q + u (k . q)``), and written back in place."""
    q, k, v, tails = _streams(p, x[:, None], (tail_q, tail_k, tail_v), dtype)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    g, beta = gates(p, x, dtype)
    if rows is None:
        rows = jnp.arange(x.shape[0])
    with jax.named_scope("update"):
        o, pool = gdn_update.gdn_update(pool, rows, q, k, v, jnp.exp(g),
                                        beta)
    return (_gate_out(p, o, x, dtype), pool, *tails)
