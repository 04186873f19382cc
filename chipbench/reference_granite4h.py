"""Plain float32 reference of the `granitemoehybrid` model without experts
(granite-4.0-h-micro: Mamba-2 layers, arXiv:2405.21060, beside full
grouped-query attention without a position encoding), and the weights.

Straightforward ``jax.numpy`` following the layer equations: no cache, no
batching, no kernels, no chunks; every matrix product in float32 at
``Precision.HIGHEST``, the bfloat16 weights upcast inside each product; the
Mamba-2 recurrence **position by position** (a plain ``lax.scan`` carrying
the ``(H, P, N)`` states of all heads, not the chunked form); the causal
convolution written as a sum over its taps; attention as explicit masked
softmaxes over blocks of queries; the head over the positions asked for
only, and over the vocabulary in blocks of ids.  It imports nothing of
the program (``blendjax``): the weights come from :func:`make_params`
here, which the program and the reference are both given.

A model is described by the published (Hugging Face) keys of its
configuration file.  ``d`` is ``hidden_size``; ``H``, ``P`` and ``N`` are
``mamba_n_heads``, ``mamba_d_head`` and ``mamba_d_state``; ``d_inner = H
P`` (``mamba_expand d``).  The four muP scalars are the configuration's::

    x0 = embedding_multiplier * E[ids]
    h  <- x + residual_multiplier * Mixer(RMSNorm(x))
    out <- h + residual_multiplier * ((up * silu(gate)) W_down)(RMSNorm(h))
    logits = RMSNorm(x) E^T / logits_scaling      (tied; eps rms_norm_eps)

The mixer by layer is the configuration's own ``layer_types``.

- Mamba-2 (``mamba``): ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC)
  + b)``, a depthwise causal convolution of ``mamba_d_conv`` taps over
  ``x | B | C`` (one group of ``B`` and ``C``, ``mamba_n_groups`` 1);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``S_h <-
  exp(dt_h A_h) S_h + dt_h x_h B^T``, ``y_h = S_h C + D_h x_h``; out ``=
  RMSNorm_d_inner(y * silu(z)) W_out``.
- Attention (``attention``): ``q, k, v = x Wq, x Wk, x Wv`` (grouped, no
  bias), no rotation (``position_embedding_type: nope``), causal softmax
  at ``attention_multiplier``, ``Wo``.

**Departures from the published description** (the configuration file
lists each under ``assumed``): ``head_dim`` is null and is read as
``hidden_size / num_attention_heads`` (64); the weights are seeded (below).

The weights' layout is the program's (``blendjax.models.mamba2`` and
``blendjax.models.seqformer`` document it): the Mamba-2 input projection
one matrix ``(d, 2 d_inner + 2N + H)``, its taps ``(taps, d_inner + 2N)``,
the attention head-major ``(d, H, Dh)``, the feed-forward as ``gate`` /
``up`` / ``down``.

Besides the logits, :func:`ssd_states` gives every Mamba-2 layer's state
after the first ``n`` ids: what a served row's pool entries hold once it has
taken them, which the cell's driver reads out of the pool and holds
against it.

``quant="int8"`` is the control: the same mathematics with both operands of
every matrix product rounded to 8 bits (per tensor, symmetric), the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.flops_granite4h import layer_kinds, sizes
from chipbench.reference import seed_key
from chipbench.reference_olmohybrid import _mm, _Static, feed_forward, rms_norm
# the tied table's view in 8 blocks of ids, as the other tied model's
from chipbench.reference_phi4flash import served_view  # noqa: F401
from chipbench.reference_sarvam import reply_gaps  # noqa: F401 (re-export)

Q_BLOCK = 256      # queries per block of attention scores


# -- the weights ---------------------------------------------------------


def leaf_shapes(model):
    """``(path, shape, scale, kind)`` of every leaf, in the order they are
    seeded.  ``kind``: ``normal`` (scale is the standard deviation),
    ``norm`` (1 + 0.02 normal), ``ones`` (float32), and the published
    Mamba-2 initialisation ``a_log`` (``log U(1, 16)``, float32) and
    ``dt_bias`` (the inverse softplus of steps log-uniform in 1e-3 ..
    1e-1, float32).  The table is normal at ``logits_scaling / sqrt(d)``,
    so that the logits, over ``logits_scaling``, spread near 1."""
    d, heads, kv, dh, h, p, n, taps = sizes(model)
    f, vocab = model["shared_intermediate_size"], model["vocab_size"]
    d_inner, conv = h * p, h * p + 2 * n
    out = [(("embed", "table"), (vocab, d),
            float(model["logits_scaling"]) / math.sqrt(d), "normal")]
    for i, kind in enumerate(layer_kinds(model)):
        blk = ("blocks", i)
        out += [(blk + ("ln1", "scale"), (d,), 0.0, "norm")]
        if kind == "mamba":
            m = blk + ("ssd",)
            out += [
                (m + ("w_in",), (d, 2 * d_inner + 2 * n + h), d ** -0.5,
                 "normal"),
                (m + ("conv_w",), (taps, conv), taps ** -0.5, "normal"),
                (m + ("conv_b",), (conv,), 0.1, "normal"),
                (m + ("dt_bias",), (h,), 0.0, "dt_bias"),
                (m + ("a_log",), (h,), 0.0, "a_log"),
                (m + ("d_skip",), (h,), 0.0, "ones"),
                (m + ("norm", "scale"), (d_inner,), 0.0, "norm"),
                (m + ("w_out",), (d_inner, d), d_inner ** -0.5, "normal"),
            ]
        else:
            out += [
                (blk + ("wq",), (d, heads, dh), d ** -0.5, "normal"),
                (blk + ("wk",), (d, kv, dh), d ** -0.5, "normal"),
                (blk + ("wv",), (d, kv, dh), d ** -0.5, "normal"),
                (blk + ("wo",), (heads, dh, d), (heads * dh) ** -0.5,
                 "normal"),
            ]
        out += [(blk + ("ln2", "scale"), (d,), 0.0, "norm"),
                (blk + ("mlp", "gate"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "up"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "down"), (f, d), f ** -0.5, "normal")]
    out += [(("ln_f", "scale"), (d,), 0.0, "norm")]
    if not model.get("tie_word_embeddings"):
        out += [(("head", "w"), (d, vocab), d ** -0.5, "normal")]
    return out


@functools.partial(jax.jit, static_argnames=("shape", "scale", "kind",
                                             "dtype"))
def _leaf(key, *, shape, scale, kind, dtype):
    f32 = jnp.float32
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "ones":
        return jnp.ones(shape, f32)
    x = jax.random.normal(key, shape, f32)
    if kind == "norm":
        return (1.0 + 0.02 * x).astype(dtype)
    return (x * scale).astype(dtype)


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


# -- the model -----------------------------------------------------------


def ssd_recurrence(x, dt, a, b, c, state=None):
    """The recurrence position by position: ``x`` (T, H, P), ``dt`` (T,
    H), ``a`` (H,) (``A``), ``b, c`` (T, N) -> ``(y (T, H, P), the state
    after T)``, ``y`` without the skip, from ``state`` (H, P, N), zeros by
    default."""
    if state is None:
        state = jnp.zeros((*x.shape[1:], b.shape[-1]), jnp.float32)

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.sum(s * c_t[None, None, :], -1)

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


def mamba2(p, x, model, quant=None, upto=None):
    """``x`` (T, d) -> ((T, d), the state (H, P, N) after the first
    ``upto`` positions, all of them by default), from an empty state and
    an empty tail.  Past ``upto`` ``dt`` is 0: the state stays as it was."""
    f32 = jnp.float32
    t = x.shape[0]
    _, _, _, _, h, pw, n, taps = sizes(model)
    d_inner = h * pw
    proj = _mm("td,de->te", x, p["w_in"], quant)
    z, xbc = proj[:, :d_inner], proj[:, d_inner:2 * d_inner + 2 * n]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc])
    w = p["conv_w"].astype(f32)
    xbc = jax.nn.silu(p["conv_b"].astype(f32)
                      + sum(padded[i:i + t] * w[i] for i in range(taps)))
    xs = xbc[:, :d_inner].reshape(t, h, pw)
    b, c = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(proj[:, -h:] + p["dt_bias"].astype(f32))
    if upto is not None:
        dt = jnp.where(jnp.arange(t)[:, None] < upto, dt, 0.0)
    y, state = ssd_recurrence(xs, dt, -jnp.exp(p["a_log"].astype(f32)), b,
                              c)
    y = y + p["d_skip"].astype(f32)[:, None] * xs
    y = rms_norm(p["norm"], y.reshape(t, d_inner) * jax.nn.silu(z),
                 float(model["rms_norm_eps"]))
    return _mm("te,ed->td", y, p["w_out"], quant), state


def attention(blk, x, model, quant=None):
    """Causal softmax attention of ``x`` (T, d) at
    ``attention_multiplier``, unrotated, in blocks of queries."""
    t = x.shape[0]
    q, k, v = (_mm("td,dhk->thk", x, blk[n], quant)
               for n in ("wq", "wk", "wv"))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    at = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        keep = at[None, :hi] <= at[lo:hi, None]
        s = (_mm("qhk,shk->hqs", q[lo:hi], k[:hi], quant)
             * float(model["attention_multiplier"]))
        w = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        outs.append(_mm("hqs,shk->qhk", w, v[:hi], quant))
    return _mm("thk,hkd->td", jnp.concatenate(outs), blk["wo"], quant)


_LAYER_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "mamba_n_heads", "mamba_d_head", "mamba_d_state",
               "mamba_d_conv", "mamba_n_groups", "rms_norm_eps",
               "attention_multiplier", "residual_multiplier")


@functools.partial(jax.jit, static_argnames=("kind", "model", "quant"))
def layer(blk, x, upto, *, kind, model, quant=None):
    """One layer over ``x`` (T, d) -> (its output, a Mamba-2 layer's state
    after the first ``upto`` positions or None).  Jitted by kind, so that
    the layers of one kind share one compilation."""
    eps = float(model["rms_norm_eps"])
    scale = float(model["residual_multiplier"])
    h = rms_norm(blk["ln1"], x, eps)
    state = None
    if kind == "mamba":
        out, state = mamba2(blk["ssd"], h, model, quant, upto)
    else:
        out = attention(blk, h, model, quant)
    x = x + scale * out
    return x + scale * feed_forward(blk["mlp"], rms_norm(blk["ln2"], x, eps),
                                    quant), state


def _layers(params, model, ids, upto, quant):
    """(the last layer's output (T, d), the Mamba-2 layers' states after
    the first ``upto`` positions, in layer order)."""
    x = (params["embed"]["table"][ids].astype(jnp.float32)
         * float(model["embedding_multiplier"]))
    static = _Static({k: model[k] for k in _LAYER_KEYS})
    states = []
    for kind, blk in zip(layer_kinds(model), params["blocks"]):
        x, state = layer(blk, x, jnp.int32(upto), kind=kind, model=static,
                         quant=quant)
        if state is not None:
            states.append(state)
    return x, states


def hidden(params, model, ids, quant=None):
    """(T,) int ids -> (T, d) float32: the head's input, the final
    RMSNorm's output over ``logits_scaling`` (the logits are linear in
    it)."""
    x, _ = _layers(params, model, ids, len(ids), quant)
    return (rms_norm(params["ln_f"], x, float(model["rms_norm_eps"]))
            / float(model["logits_scaling"]))


def ssd_states(params, model, ids, upto, quant=None):
    """The Mamba-2 layers' states ``(H, P, N)`` float32 after the first
    ``upto`` of ``ids`` (T,), in layer order: what a served row holds once
    it has taken those ids.  The ids past ``upto`` pad ``ids`` to a length
    already compiled and change nothing."""
    return _layers(params, model, ids, upto, quant)[1]


def logits_of(params, x, quant=None):
    """(N, d) -> (N, vocab) float32 through the tied embedding, whole."""
    if "head" in params:
        return _mm("nd,dv->nv", x, params["head"]["w"], quant)
    return _mm("nd,vd->nv", x, params["embed"]["table"], quant)


def forward(params, model, ids, quant=None):
    """(T,) int ids -> (T, vocab) float32 logits, causal."""
    return logits_of(params, hidden(params, model, ids, quant), quant)
