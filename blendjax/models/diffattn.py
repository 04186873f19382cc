"""Differential attention (arXiv:2410.05258) with grouped heads.

Adjacent heads pair: query pair ``j`` is heads ``(2j, 2j + 1)``, K/V pair
``i`` is K/V heads ``(2i, 2i + 1)``, and query pair ``j`` reads K/V pair
``j // g`` (``g = H / Hkv``).  With ``V = [v1 | v2]`` (``2 Dh`` wide)::

    a1 = softmax(q1 k1^T / sqrt(Dh)) V      a2 = softmax(q2 k2^T / sqrt(Dh)) V
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    o_j = (1 - lam_init) RMSNorm(a1 - lam a2)          (2 Dh wide, eps 1e-5)

and the ``H/2 x 2 Dh`` outputs, read as ``H x Dh``, go through ``wo``.

**The pair is the cached unit.**  Keys and values are kept as pairs,
``(..., Hkv / 2, 2 Dh)``: ``[k_2i | k_2i+1]`` and ``[v_2i | v_2i+1]``, a
free view of ``(..., Hkv, Dh)`` whose minor axis is whole lanes (128 at
``Dh`` 64) and whose value row *is* ``V``.  Both score maps then run
against the same key row: ``q1`` is the query pair with its second half
zeroed, ``q2`` the pair with its first half zeroed, so one product over
``2 Dh`` gives ``q1 . k1`` or ``q2 . k2`` and any attention that takes
``(q, k, v)`` of one head size computes the two maps (:func:`attend`
hands them to the caller's ``attn_fn``, flash or plain).

Parameters of a block (no biases)::

    wq (d, H, Dh)   wk, wv (d, Hkv, Dh)   wo (H, Dh, d)
    diff {"lq1", "lk1", "lq2", "lk2": (Dh,) float32,
          "subln": {"scale": (2 Dh,)}, "spec": DiffSpec (static)}

A block without ``wk``/``wv`` is a **cross** layer: it projects a query
only and attends over keys and values another layer cached.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from blendjax.models.layers import rms_norm, scaled_normal

SUBLN_EPS = 1e-5


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class DiffSpec:
    """What the shapes of a differential block do not say: the layer's
    ``lam_init`` and, for a window layer, how many positions (its own
    included) a query sees."""

    lam_init: float
    window: int | None = None


def lam_init_of(layer):
    """``0.8 - 0.6 exp(-0.3 l)`` of the 0-based layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def init(key, d_model, n_heads, n_kv_heads, head_dim, spec, cross=False,
         dtype=jnp.float32):
    kq, kk, kv, ko, kl = jax.random.split(key, 5)
    p = {
        "wq": scaled_normal(kq, (d_model, n_heads, head_dim), d_model, dtype),
        "wo": scaled_normal(ko, (n_heads, head_dim, d_model),
                            n_heads * head_dim, dtype),
        "diff": {
            **{name: 0.1 * jax.random.normal(k, (head_dim,))
               for name, k in zip(("lq1", "lk1", "lq2", "lk2"),
                                  jax.random.split(kl, 4))},
            "subln": {"scale": jnp.ones((2 * head_dim,), dtype)},
            "spec": spec,
        },
    }
    if not cross:
        p["wk"] = scaled_normal(kk, (d_model, n_kv_heads, head_dim), d_model,
                                dtype)
        p["wv"] = scaled_normal(kv, (d_model, n_kv_heads, head_dim), d_model,
                                dtype)
    return p


def _pairs(x):
    """``(..., H, Dh)`` -> pair-major ``(..., H / 2, 2 Dh)``."""
    return x.reshape(*x.shape[:-2], x.shape[-2] // 2, 2 * x.shape[-1])


def project_q(p, h, dtype):
    """Normed input ``h`` (..., d) -> query pairs (..., H / 2, 2 Dh)."""
    return _pairs(jnp.einsum("...d,dhk->...hk", h.astype(dtype),
                             p["wq"].astype(dtype)))


def project_kv(p, h, dtype):
    """Normed input ``h`` (..., d) -> ``(k pairs, v pairs)``, each (...,
    Hkv / 2, 2 Dh): what the cache keeps of a position."""
    return tuple(_pairs(jnp.einsum("...d,dhk->...hk", h.astype(dtype),
                                   p[n].astype(dtype))) for n in ("wk", "wv"))


def _halves(q):
    """Query pairs -> ``(q1, q2)``: each the pair with the other member's
    half zeroed, so that a product against a key pair is ``q1 . k1``
    (``q2 . k2``)."""
    dh = q.shape[-1] // 2
    first = jnp.arange(2 * dh) < dh
    return jnp.where(first, q, 0), jnp.where(first, 0, q)


def _finish(p, a1, a2, dtype):
    """``(1 - lam_init) RMSNorm(a1 - lam a2)`` through ``wo``; ``a1``,
    ``a2`` (..., H / 2, 2 Dh)."""
    d = p["diff"]
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(d["lq1"].astype(f32) * d["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(d["lq2"].astype(f32) * d["lk2"].astype(f32)))
           + d["spec"].lam_init)
    o = rms_norm(d["subln"]["scale"], a1.astype(f32) - lam * a2.astype(f32),
                 eps=SUBLN_EPS) * (1.0 - d["spec"].lam_init)
    h, dh, _ = p["wo"].shape
    o = o.reshape(*o.shape[:-2], h, dh)
    return jnp.einsum("...hk,hkd->...d", o.astype(dtype),
                      p["wo"].astype(dtype))


def attend(p, q, k, v, dtype, attn_fn):
    """A whole sequence: query pairs (B, T, H / 2, 2 Dh) over key and
    value pairs (B, T, Hkv / 2, 2 Dh) -> (B, T, d).  ``attn_fn(q, k, v,
    scale, window)`` is causal attention over heads of one size with
    grouped K/V heads (flash or plain); it sees the two maps as ``H``
    query heads over ``Hkv`` K/V heads, the first half of them ``q1``
    against the key pairs and the second ``q2`` against the same."""
    with jax.named_scope("diff"):
        n = q.shape[2]
        out = attn_fn(
            jnp.concatenate(_halves(q), axis=2),
            jnp.concatenate([k, k], axis=2), jnp.concatenate([v, v], axis=2),
            (q.shape[-1] // 2) ** -0.5, p["diff"]["spec"].window)
        return _finish(p, out[:, :, :n], out[:, :, n:], dtype)


def attend_one(p, q, kc, vc, pos, dtype):
    """One query pair-set a row, ``q`` (B, H / 2, 2 Dh), over that row's
    cached keys and values ``kc``/``vc``, flat ``(B, C, Hkv * Dh)`` rows,
    at position ``pos`` (B,) -> (B, d).  The cache is a ring written at
    ``p % C``; a slot is masked by the absolute position it holds (as
    ``seqformer._attn_one`` masks it), which on a ring of ``window``
    slots IS the window; ``spec.window`` masks besides where the ring
    is longer.

    Each K/V pair is read where it lies, as the whole-lane columns ``[i
    * 2 Dh, (i + 1) * 2 Dh)`` of the rows: seen as ``(B, C, Hkv / 2, 2
    Dh)`` the rows would be re-laid pair-major before every product (on
    the chip a fifth of the step's device time)."""
    b, c, _ = kc.shape
    width = q.shape[-1]
    n_kv = kc.shape[-1] // width
    window = p["diff"]["spec"].window
    with jax.named_scope("diff"):
        q1, q2 = _halves(q)
        # (B, Hkv / 2, 2 maps x G query pairs, 2 Dh)
        qs = jnp.stack([q1.reshape(b, n_kv, -1, width),
                        q2.reshape(b, n_kv, -1, width)], axis=2)
        g = qs.shape[3]
        qs = qs.reshape(b, n_kv, 2 * g, width)
        p_col = pos[:, None]
        slot_pos = p_col - ((p_col - jnp.arange(c)[None]) % c)
        keep = slot_pos >= 0
        if window is not None:
            keep = jnp.logical_and(keep, slot_pos > p_col - window)
        outs = []
        for i in range(n_kv):
            cols = slice(i * width, (i + 1) * width)
            s = jnp.einsum("bme,bce->bmc", qs[:, i], kc[..., cols].astype(
                dtype), preferred_element_type=jnp.float32)
            s = jnp.where(keep[:, None], s * (width // 2) ** -0.5, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum(
                "bmc,bce->bme", w.astype(dtype), vc[..., cols].astype(dtype),
                preferred_element_type=jnp.float32))
        a = jnp.stack(outs, axis=1).reshape(b, n_kv, 2, g, width)
        return _finish(p, a[:, :, 0].reshape(b, -1, width),
                       a[:, :, 1].reshape(b, -1, width), dtype)
