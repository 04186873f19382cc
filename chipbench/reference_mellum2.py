"""Plain float32 reference of the `mellum` model (Mellum2-12B-A2.5B:
grouped-query attention, three sliding-window layers to every full layer,
each kind with a rotary embedding of its own, and softmax-routed experts in
every layer), and the weights.

Straightforward ``jax.numpy`` following the layer equations: no cache, no
batching, no kernels, no ring; every matrix product in float32 at
``Precision.HIGHEST``, the bfloat16 weights upcast inside each product;
attention as explicit masked softmaxes over blocks of queries, the window a
mask on absolute positions; both rotations written out here in float64 from
the published ``rope_parameters``; the held experts one at a time over every
token; the head over the positions asked for only, and over the vocabulary
in blocks of ids.  It imports nothing of the program (``blendjax``): the
weights come from :func:`make_params` here, which the program and the
reference are both given.

A model is described by the published (Hugging Face) keys of its
configuration file, with ``num_experts_held`` (and ``held_first``, 0 by
default) beside ``num_experts``: the reference routes over all
``num_experts`` and computes the experts ``[held_first, held_first +
num_experts_held)``, the same share the program holds.  Every layer::

    h <- x + Attn(RMSNorm(x));   out <- h + MoE(RMSNorm(h))
    logits = RMSNorm(x) W_head                     (eps rms_norm_eps, untied)

- Attention: ``q, k, v = x Wq, x Wk, x Wv`` (``num_attention_heads`` query
  heads and ``num_key_value_heads`` K/V heads of ``head_dim``), an RMSNorm
  over each head of ``q`` and of ``k``, then the rotation of the layer's
  kind (half-split pairs); causal softmax at ``1 / sqrt(head_dim)``; a
  ``sliding_attention`` layer's query at ``p`` sees positions ``p -
  sliding_window + 1 .. p``; ``Wo``.
- Rotation: ``rope_type: default`` is ``theta^(-2i/head_dim)``; ``yarn``
  blends that with it over ``factor`` by the linear ramp between the
  correction dimensions of ``beta_fast`` and ``beta_slow`` rotations over
  ``original_max_position_embeddings`` (floored, ceiled and clipped to
  ``[0, head_dim - 1]``, Hugging Face's ``truncate``), and the cos and sin
  tables are multiplied by ``attention_factor``.
- Experts: ``p = softmax(h W_r)`` over all ``num_experts``, float32; the
  ``num_experts_per_tok`` largest, renormalised to sum 1 under
  ``norm_topk_prob``; ``y = sum_{e selected and held} p_e E_e(h)``, ``E(h) =
  W_down(silu(W_gate h) * (W_up h))``; no shared expert, no bias, nothing
  dropped.

**Departures from the published description** (the configuration file
lists each under ``assumed``): the config has no q/k-norm key, and a
per-head RMSNorm of q and k before the rotation is taken (the keys are a
Qwen3-style attention's); the multi-token prediction head is left out.

``quant="int8"`` is the control: the same mathematics with both operands of
every matrix product rounded to 8 bits (per tensor, symmetric), the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops_mellum2 import layer_kinds
from chipbench.reference import _fake_int8, seed_key
from chipbench.reference_olmohybrid import served_view  # noqa: F401
from chipbench.reference_sarvam import reply_gaps  # noqa: F401 (re-export)

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256      # queries per block of attention scores
_TYPES = {"window": "sliding_attention", "full": "full_attention"}


# -- the weights ------------------------------------------------------------------


def leaf_shapes(model):
    """``(path, shape, scale, kind)`` of every leaf, in the order they are
    seeded.  ``kind``: ``normal`` (scale is the standard deviation),
    ``norm`` (1 + 0.02 normal), ``f32`` (normal, kept in float32: the
    router)."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv, dh = model["num_key_value_heads"], model["head_dim"]
    f, held = model["moe_intermediate_size"], model["num_experts_held"]
    vocab = model["vocab_size"]
    out = [(("embed", "table"), (vocab, d), 1.0, "normal")]
    for i in range(model["num_hidden_layers"]):
        blk = ("blocks", i)
        moe = blk + ("moe",)
        out += [
            (blk + ("ln1", "scale"), (d,), 0.0, "norm"),
            (blk + ("wq",), (d, heads, dh), d ** -0.5, "normal"),
            (blk + ("wk",), (d, kv, dh), d ** -0.5, "normal"),
            (blk + ("wv",), (d, kv, dh), d ** -0.5, "normal"),
            (blk + ("wo",), (heads, dh, d), (heads * dh) ** -0.5, "normal"),
            (blk + ("q_norm", "scale"), (dh,), 0.0, "norm"),
            (blk + ("k_norm", "scale"), (dh,), 0.0, "norm"),
            (blk + ("ln2", "scale"), (d,), 0.0, "norm"),
            (moe + ("router", "w"), (d, model["num_experts"]), d ** -0.5,
             "f32"),
            (moe + ("gate",), (held, d, f), d ** -0.5, "normal"),
            (moe + ("up",), (held, d, f), d ** -0.5, "normal"),
            (moe + ("down",), (held, f, d), f ** -0.5, "normal"),
        ]
    return out + [(("ln_f", "scale"), (d,), 0.0, "norm"),
                  (("head", "w"), (d, vocab), d ** -0.5, "normal")]


@functools.partial(jax.jit, static_argnames=("shape", "scale", "kind",
                                             "dtype"))
def _leaf(key, *, shape, scale, kind, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + 0.02 * x).astype(dtype)
    return (x * scale).astype(jnp.float32 if kind == "f32" else dtype)


def make_params(model, seed, dtype=jnp.bfloat16):
    """The parameter tree, made on the device leaf by leaf from the seed
    (nothing passes through the host).  No static entries: the program's
    are the caller's to add."""
    tree = {"blocks": [{} for _ in range(model["num_hidden_layers"])]}
    leaves = leaf_shapes(model)
    keys = jax.random.split(seed_key(seed), len(leaves))
    for key, (path, shape, scale, kind) in zip(keys, leaves):
        node = tree
        for name in path[:-1]:
            node = node[name] if isinstance(name, int) \
                else node.setdefault(name, {})
        node[path[-1]] = _leaf(key, shape=shape, scale=float(scale),
                               kind=kind, dtype=dtype)
    return tree


# -- the rotations ----------------------------------------------------------------


def inv_freq(rope, dh):
    """``(inverse frequencies (dh / 2,) float64, the tables' factor)`` of
    one kind's ``rope_parameters``."""
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dh * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dh - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0.0, 1.0)
    extrapolated = 1.0 - ramp   # the share kept at the plain frequency
    blended = plain / factor * (1.0 - extrapolated) + plain * extrapolated
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return blended, float(scale)


def rope_tables(model, kind, t):
    """``(cos, sin)`` (t, head_dim / 2) float32 of a layer kind at
    positions ``0 .. t - 1``, each times the kind's factor."""
    freqs, scale = inv_freq(model["rope_parameters"][_TYPES[kind]],
                            model["head_dim"])
    ang = np.arange(t, dtype=np.float64)[:, None] * freqs[None]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _rotate(x, cos, sin):
    """Half-split rotation of (T, H, Dh) by (T, Dh / 2) tables."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


# -- the model -------------------------------------------------------------------


def _mm(eq, a, b, quant=None):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "int8":
        a, b = _fake_int8(a), _fake_int8(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(p, x, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * p["scale"].astype(jnp.float32))


def attention(blk, x, cos, sin, window, eps, quant=None):
    """Causal grouped-query attention of ``x`` (T, d): per-head q/k norms,
    the rotation by ``cos``/``sin``, a window of ``window`` positions (its
    own included; None: all), in blocks of queries."""
    t = x.shape[0]
    q, k, v = (_mm("td,dhk->thk", x, blk[n], quant)
               for n in ("wq", "wk", "wv"))
    heads, dh = q.shape[1:]
    q = _rotate(rms_norm(blk["q_norm"], q, eps), cos, sin)
    k = _rotate(rms_norm(blk["k_norm"], k, eps), cos, sin)
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    at = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        start = 0 if window is None else max(0, lo - window + 1)
        rows, cols = at[lo:hi, None], at[None, start:hi]
        keep = cols <= rows
        if window is not None:
            keep = jnp.logical_and(keep, cols > rows - window)
        s = _mm("qhk,shk->hqs", q[lo:hi], k[start:hi], quant) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        outs.append(_mm("hqs,shk->qhk", w, v[start:hi], quant))
    return _mm("thk,hkd->td", jnp.concatenate(outs), blk["wo"], quant)


def route(router, h, top_k, renorm, quant=None):
    """(T, num_experts) weights: the ``top_k`` largest of ``softmax(h
    W_r)``, renormalised to sum 1 where ``renorm``, 0 elsewhere."""
    probs = jax.nn.softmax(_mm("td,de->te", h, router["w"], quant), -1)
    w, sel = jax.lax.top_k(probs, top_k)
    if renorm:
        w = w / w.sum(-1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], sel].set(w)


def expert(gate, up, down, h, quant=None):
    """``down(silu(gate h) * (up h))``."""
    a = jax.nn.silu(_mm("td,df->tf", h, gate, quant)) \
        * _mm("td,df->tf", h, up, quant)
    return _mm("tf,fd->td", a, down, quant)


def moe(p, h, top_k, renorm, first, quant=None):
    """The held share of the expert layer: every held expert over every
    token, one expert at a time, under its weight (0 for a token that did
    not choose it).  What the absent experts would add is left out."""
    held = p["gate"].shape[0]
    g = route(p["router"], h, top_k, renorm, quant)[:, first:first + held]

    def one(y, e):
        gate, up, down, g_e = e
        return y + g_e[:, None] * expert(gate, up, down, h, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                        (p["gate"], p["up"], p["down"], g.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "window", "eps", "top_k", "renorm", "first", "quant"))
def layer(blk, x, cos, sin, *, window, eps, top_k, renorm, first,
          quant=None):
    """One layer over ``x`` (T, d).  Jitted by kind, so that the layers of
    one kind share one compilation."""
    x = x + attention(blk, rms_norm(blk["ln1"], x, eps), cos, sin, window,
                      eps, quant)
    return x + moe(blk["moe"], rms_norm(blk["ln2"], x, eps), top_k, renorm,
                   first, quant)


def hidden(params, model, ids, quant=None):
    """(T,) int ids -> (T, d) float32: the final RMSNorm's output."""
    t = ids.shape[0]
    eps = float(model["rms_norm_eps"])
    tables = {kind: rope_tables(model, kind, t) for kind in _TYPES}
    x = params["embed"]["table"][ids].astype(jnp.float32)
    for kind, blk in zip(layer_kinds(model), params["blocks"]):
        x = layer(blk, x, *tables[kind],
                  window=model["sliding_window"] if kind == "window"
                  else None,
                  eps=eps, top_k=model["num_experts_per_tok"],
                  renorm=bool(model["norm_topk_prob"]),
                  first=model.get("held_first", 0), quant=quant)
    return rms_norm(params["ln_f"], x, eps)


def logits_of(params, x, quant=None):
    """(N, d) -> (N, vocab) float32 through the untied head, whole."""
    return _mm("nd,dv->nv", x, params["head"]["w"], quant)


def forward(params, model, ids, quant=None):
    """(T,) int ids -> (T, vocab) float32 logits, causal."""
    return logits_of(params, hidden(params, model, ids, quant), quant)
