"""Latent attention (MLA): one low-rank row per position in the cache.

A position's key and value are not kept per head.  The block projects
the normed input down to a ``kv_rank``-wide latent ``c`` and one rope key
``k_pe`` shared by all heads, and **the cache row is** ``[RMSNorm(c) |
rot(k_pe)]`` (576 wide at the published sizes, against 64 heads x 320
for per-head keys and values), zero-padded to whole lanes
(:func:`row_width`: 640).  Two paths compute the same attention:

- **expanded** (:func:`attend_expanded`; prefill): every cached row is
  expanded to per-head ``k_nope`` and ``v`` through ``wukv`` and plain
  causal attention runs over them, a block of queries at a time, so that
  no ``heads x T x T`` score tensor larger than a block exists;
- **absorbed** (:func:`attend_absorbed`; decode): ``wukv``'s key half is
  folded into the query (``qt_h = q_nope_h W_uk_h^T``) and its value half
  applied after the weighted sum of latents, so a step reads the latent
  rows and never forms a per-head key or value of a cached position.

Parameters of a block's ``"mla"`` entry (no biases)::

    wq     (d, H, nope + rope)    wdkv   (d, kv_rank + rope)
    c_norm {"scale": (kv_rank,)}  wukv   (kv_rank, H, nope + v)
    wo     (H, v, d)              spec   MlaSpec (static)

Rope pairs are taken half-split, as :func:`layers.apply_rope` does.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from blendjax.models.layers import (
    apply_rope,
    apply_rope_rows,
    rms_norm,
    rope_table,
    scaled_normal,
    yarn_mscale,
)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """What the shapes of an ``"mla"`` entry do not say."""

    rope_dim: int
    rope_base: float = 10000.0
    #: ``(factor, beta_fast, beta_slow, original_max, mscale,
    #: mscale_all_dim)`` or None for plain rope
    yarn: tuple | None = None


def init(key, d_model, n_heads, kv_rank, nope_dim, v_dim, spec,
         dtype=jnp.float32):
    kq, kd, ku, ko = jax.random.split(key, 4)
    return {
        "wq": scaled_normal(kq, (d_model, n_heads, nope_dim + spec.rope_dim),
                            d_model, dtype),
        "wdkv": scaled_normal(kd, (d_model, kv_rank + spec.rope_dim),
                              d_model, dtype),
        "c_norm": {"scale": jnp.ones((kv_rank,), dtype)},
        "wukv": scaled_normal(ku, (kv_rank, n_heads, nope_dim + v_dim),
                              kv_rank, dtype),
        "wo": scaled_normal(ko, (n_heads, v_dim, d_model), n_heads * v_dim,
                            dtype),
        "spec": spec,
    }


#: queries per block of the expanded path: at 64 heads a block's float32
#: scores against 1024 keys are 67 MB
_Q_BLOCK = 256

#: the TPU's lane count.  A cache tensor whose minor axis is not a
#: multiple of it (576 = 4.5 x 128) is laid out positions-minor by the
#: TPU compiler, and the step then copies the whole pool twice a layer
#: to write one position and to hand it back (compiled for a described
#: v5e: two pool-shaped `copy` ops a layer); padded to 640 it is served
#: in place.
_LANES = 128


def row_width(p):
    """Width of a cache row: ``kv_rank + rope`` rounded up to whole
    lanes, the padding zero."""
    return -(-p["wdkv"].shape[1] // _LANES) * _LANES


def rope(p, positions):
    spec = p["spec"]
    return rope_table(positions, spec.rope_dim, spec.rope_base, spec.yarn)


def softmax_scale(p):
    """``q_head_dim^-0.5 * m^2``, ``m`` YaRN's correction at
    ``mscale_all_dim`` (1 without YaRN)."""
    m = 1.0
    if p["spec"].yarn is not None:
        m = yarn_mscale(p["spec"].yarn[0], p["spec"].yarn[5])
    return p["wq"].shape[-1] ** -0.5 * m * m


def project(p, h, cos, sin, dtype):
    """Normed input ``h`` (B, T, d) or (B, d) -> ``(q_nope, q_pe, row)``:
    the query's two parts per head and the position's cache row
    ``[RMSNorm(c) | rot(k_pe)]``.  ``cos``/``sin`` are (T, rope/2) for a
    sequence and (B, rope/2), one position a row, for a single step."""
    rope_dim = p["spec"].rope_dim
    rank = p["c_norm"]["scale"].shape[0]
    q = jnp.einsum("...d,dhk->...hk", h.astype(dtype), p["wq"].astype(dtype))
    ckpe = h.astype(dtype) @ p["wdkv"].astype(dtype)
    c = rms_norm(p["c_norm"]["scale"], ckpe[..., :rank])
    k_pe = ckpe[..., None, rank:]  # one head
    q_nope, q_pe = q[..., :-rope_dim], q[..., -rope_dim:]
    if h.ndim == 3:
        q_pe, k_pe = apply_rope(q_pe, cos, sin), apply_rope(k_pe, cos, sin)
    else:
        q_pe = apply_rope_rows(q_pe, cos, sin)
        k_pe = apply_rope_rows(k_pe, cos, sin)
    row = jnp.concatenate([c, k_pe[..., 0, :]], -1)
    pad = row_width(p) - row.shape[-1]
    return q_nope, q_pe, jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def _out(p, o, dtype):
    return jnp.einsum("...hv,hvd->...d", o.astype(dtype),
                      p["wo"].astype(dtype))


def attend_expanded(p, q_nope, q_pe, rows, dtype):
    """Causal attention over a whole sequence, (B, T, ...) in and
    (B, T, d) out: the rows expanded to per-head keys and values, the
    queries taken ``_Q_BLOCK`` at a time against the keys up to
    their block's end (the triangle above is never computed)."""
    t = rows.shape[1]
    rank = p["c_norm"]["scale"].shape[0]
    nope = q_nope.shape[-1]
    scale = softmax_scale(p)
    with jax.named_scope("expand"):
        kv = jnp.einsum("btr,rhk->bthk", rows[..., :rank].astype(dtype),
                        p["wukv"].astype(dtype))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k_pe = rows[..., rank:rank + q_pe.shape[-1]].astype(dtype)
        outs = []
        for lo in range(0, t, _Q_BLOCK):
            hi = min(lo + _Q_BLOCK, t)
            s = jnp.einsum("bqhk,bshk->bhqs", q_nope[:, lo:hi],
                           k_nope[:, :hi],
                           preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bqhk,bsk->bhqs", q_pe[:, lo:hi],
                               k_pe[:, :hi],
                               preferred_element_type=jnp.float32)
            keep = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None])
            w = jax.nn.softmax(jnp.where(keep, s * scale, -1e30), axis=-1)
            outs.append(jnp.einsum("bhqs,bshv->bqhv", w.astype(dtype),
                                   v[:, :hi],
                                   preferred_element_type=jnp.float32))
        o = jnp.concatenate(outs, axis=1)
    return _out(p, o, dtype)


def attend_absorbed(p, q_nope, q_pe, rows, pos, dtype):
    """One query a row, (B, H, ...), over that row's cached latents
    ``rows`` (B, C, rank + rope) at position ``pos`` (B,): the key
    expansion folded into the query, the value expansion applied to the
    weighted sum of latents.  ``rows`` is a ring written at ``p % C``;
    a slot is masked by the absolute position it holds, as
    ``seqformer._attn_one`` masks it."""
    c = rows.shape[1]
    rank = p["c_norm"]["scale"].shape[0]
    nope = q_nope.shape[-1]
    with jax.named_scope("absorb"):
        wukv = p["wukv"].astype(dtype)
        qt = jnp.einsum("bhk,rhk->bhr", q_nope, wukv[..., :nope],
                        preferred_element_type=jnp.float32).astype(dtype)
        # (B, H, row width): zeros against the row's padding
        q = jnp.concatenate([qt, q_pe], -1)
        q = jnp.pad(q, [(0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])])
        s = jnp.einsum("bhk,bck->bhc", q, rows.astype(dtype),
                       preferred_element_type=jnp.float32)
        p_col = pos[:, None]
        slot_pos = p_col - ((p_col - jnp.arange(c)[None]) % c)
        s = jnp.where((slot_pos >= 0)[:, None], s * softmax_scale(p), -1e30)
        w = jax.nn.softmax(s, axis=-1)
        u = jnp.einsum("bhc,bcr->bhr", w.astype(dtype),
                       rows[..., :rank].astype(dtype),
                       preferred_element_type=jnp.float32).astype(dtype)
        o = jnp.einsum("bhr,rhv->bhv", u, wukv[..., nope:],
                       preferred_element_type=jnp.float32)
    return _out(p, o, dtype)
