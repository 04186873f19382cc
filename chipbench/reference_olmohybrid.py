"""Plain float32 reference of the `olmo_hybrid` model (gated delta-rule
linear attention, arXiv:2412.06464, three layers in four; full softmax
attention the fourth), and the weights.

Straightforward ``jax.numpy`` following the layer equations: no cache, no
batching, no kernels, no chunks; every matrix product in float32 at
``Precision.HIGHEST``, the bfloat16 weights upcast inside each product; the
delta rule **position by position** (a plain ``lax.scan`` carrying one
``(H, dv, dk)`` state, no WY form); the causal convolution written as a sum
over its taps; attention as explicit masked softmaxes over blocks of
queries; the head over the positions asked for only, and over the
vocabulary in blocks of ids.  It imports nothing of the program
(``blendjax``): the weights come from :func:`make_params` here, which the
program and the reference are both given.

A model is described by the published (Hugging Face) keys of its
configuration file.  ``d`` is ``hidden_size``, ``H`` / ``dk`` / ``dv`` the
linear layers' ``linear_num_key_heads`` (= value heads),
``linear_key_head_dim`` and ``linear_value_head_dim``.  Every layer::

    h <- x + RMSNorm(Mixer(x));   out <- h + RMSNorm((up * silu(gate)) W_down)
    logits = RMSNorm(x) W_head                     (eps rms_norm_eps, untied)

The mixer by layer is the configuration's own ``layer_types``.

- Linear attention, per head: ``q~, k~, v~ = x Wq, x Wk, x Wv``, each through
  a depthwise causal convolution of ``linear_conv_kernel_dim`` taps and SiLU;
  ``q = l2norm(q~) / sqrt(dk)``, ``k = l2norm(k~)``; ``beta = 2 sigmoid(x
  Wb)`` (the 2 is ``linear_allow_neg_eigval``), ``alpha = exp(-exp(A_log)
  softplus(x Wa + dt_bias))``; ``S' = alpha S``, ``u = beta (v - S' k)``,
  ``S = S' + u k^T``, ``o = S q``; out ``= (RMSNorm_dv(o) * silu(x Wg)) Wo``.
- Full attention: ``q, k, v = x Wq, x Wk, x Wv``, RMSNorm over the whole
  ``q`` and the whole ``k`` before the heads are split, causal softmax at
  ``1 / sqrt(Dh)``, ``Wo``.  No rotary embedding.

**Departures from the published description** (the configuration file
lists each under ``assumed``): the config does not say where the norms
sit (taken after each sublayer and over the whole q/k projection, the
Olmo 2 / 3 convention); ``rope_parameters.rope_theta: null`` is read as no
rotary embedding; the l2 norm adds 1e-6 under its root; no projection has
a bias, the convolutions included; ``A_log`` is drawn from ``log U(1e-3,
16)`` (the published ``log U(0, 16)`` with its lower end kept off zero).

The weights' layout is the program's (``blendjax.models.deltanet`` and
``blendjax.models.seqformer`` document it): the linear layer's projections
flat ``(d, H dk)`` / ``(d, H dv)``, convolution taps ``(taps, width)``, the
full layer's head-major ``(d, H, Dh)``, the feed-forward as ``gate`` /
``up`` / ``down``.

``quant="int8"`` is the control: the same mathematics with both operands of
every matrix product rounded to 8 bits (per tensor, symmetric), the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.flops_olmohybrid import layer_kinds
from chipbench.reference import _fake_int8, seed_key
from chipbench.reference_sarvam import reply_gaps  # noqa: F401 (re-export)

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
Q_BLOCK = 256      # queries per block of attention scores
VOCAB_BLOCKS = 8   # the head runs over the vocabulary in this many blocks


# -- the weights ------------------------------------------------------------------


def leaf_shapes(model):
    """``(path, shape, scale, kind)`` of every leaf, in the order they are
    seeded.  ``kind``: ``normal`` (scale is the standard deviation),
    ``norm`` (1 + 0.02 normal), and the published layer's initialisation
    ``a_log`` (``log U(1e-3, 16)``, float32) and ``dt_bias`` (the inverse
    softplus of steps log-uniform in 1e-3 .. 1e-1, float32), so that
    ``alpha`` spans about 0.2 .. 0.999."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv, dh = model["num_key_value_heads"], model["hidden_size"] // heads
    f, vocab = model["intermediate_size"], model["vocab_size"]
    lin = model["linear_num_key_heads"]
    wide_k = lin * model["linear_key_head_dim"]
    wide_v = lin * model["linear_value_head_dim"]
    taps = model["linear_conv_kernel_dim"]
    out = [(("embed", "table"), (vocab, d), 1.0, "normal")]
    for i, kind in enumerate(layer_kinds(model)):
        blk = ("blocks", i)
        if kind == "linear":
            g = blk + ("gdn",)
            out += [
                (g + ("wq",), (d, wide_k), d ** -0.5, "normal"),
                (g + ("wk",), (d, wide_k), d ** -0.5, "normal"),
                (g + ("wv",), (d, wide_v), d ** -0.5, "normal"),
                (g + ("wg",), (d, wide_v), d ** -0.5, "normal"),
                (g + ("wo",), (wide_v, d), wide_v ** -0.5, "normal"),
                (g + ("wa",), (d, lin), d ** -0.5, "normal"),
                (g + ("wb",), (d, lin), d ** -0.5, "normal"),
                (g + ("conv_q",), (taps, wide_k), 0.5, "normal"),
                (g + ("conv_k",), (taps, wide_k), 0.5, "normal"),
                (g + ("conv_v",), (taps, wide_v), 0.5, "normal"),
                (g + ("a_log",), (lin,), 0.0, "a_log"),
                (g + ("dt_bias",), (lin,), 0.0, "dt_bias"),
                (g + ("o_norm", "scale"),
                 (model["linear_value_head_dim"],), 0.0, "norm"),
            ]
        else:
            out += [
                (blk + ("wq",), (d, heads, dh), d ** -0.5, "normal"),
                (blk + ("wk",), (d, kv, dh), d ** -0.5, "normal"),
                (blk + ("wv",), (d, kv, dh), d ** -0.5, "normal"),
                (blk + ("wo",), (heads, dh, d), (heads * dh) ** -0.5,
                 "normal"),
                (blk + ("q_norm", "scale"), (heads * dh,), 0.0, "norm"),
                (blk + ("k_norm", "scale"), (kv * dh,), 0.0, "norm"),
            ]
        out += [(blk + ("post_ln1", "scale"), (d,), 0.0, "norm"),
                (blk + ("post_ln2", "scale"), (d,), 0.0, "norm"),
                (blk + ("mlp", "gate"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "up"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "down"), (f, d), f ** -0.5, "normal")]
    return out + [(("ln_f", "scale"), (d,), 0.0, "norm"),
                  (("head", "w"), (d, vocab), d ** -0.5, "normal")]


@functools.partial(jax.jit, static_argnames=("shape", "scale", "kind",
                                             "dtype"))
def _leaf(key, *, shape, scale, kind, dtype):
    f32 = jnp.float32
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1e-3, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
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


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def gates(p, x, neg_eigval, quant=None):
    """``(alpha, beta)`` (T, H): the decay and the writing strength."""
    f32 = jnp.float32
    g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
        _mm("td,dh->th", x, p["wa"], quant) + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(_mm("td,dh->th", x, p["wb"], quant))
    return jnp.exp(g), (2.0 if neg_eigval else 1.0) * beta


def delta_rule(q, k, v, alpha, beta, state=None):
    """The recurrence position by position: ``q, k`` (T, H, dk), ``v`` (T,
    H, dv), ``alpha, beta`` (T, H) -> ``(o (T, H, dv), the state after
    T)``, from ``state`` (H, dv, dk), zeros by default."""
    if state is None:
        state = jnp.zeros((q.shape[1], v.shape[-1], q.shape[-1]), jnp.float32)

    def step(s, at):
        q_t, k_t, v_t, a_t, b_t = at
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.sum(s * k_t[:, None, :], -1))
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, jnp.sum(s * q_t[:, None, :], -1)

    state, o = jax.lax.scan(step, state, (q, k, v, alpha, beta))
    return o, state


def linear_attention(p, x, model, quant=None):
    """``x`` (T, d) -> (T, d), from an empty state and empty tails."""
    f32 = jnp.float32
    t = x.shape[0]
    heads, dk = model["linear_num_key_heads"], model["linear_key_head_dim"]
    taps = model["linear_conv_kernel_dim"]

    def stream(name):
        y = _mm("td,de->te", x, p["w" + name], quant)
        padded = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1]), f32), y])
        w = p["conv_" + name].astype(f32)
        y = sum(padded[i:i + t] * w[i] for i in range(taps))
        return jax.nn.silu(y).reshape(t, heads, -1)

    q, k, v = stream("q"), stream("k"), stream("v")
    alpha, beta = gates(p, x, model["linear_allow_neg_eigval"], quant)
    o, _ = delta_rule(l2norm(q) * dk ** -0.5, l2norm(k), v, alpha, beta)
    o = rms_norm(p["o_norm"], o, float(model["rms_norm_eps"]))
    gate = jax.nn.silu(_mm("td,de->te", x, p["wg"], quant))
    return _mm("te,ed->td", o.reshape(t, -1) * gate, p["wo"], quant)


def full_attention(blk, x, model, quant=None):
    """Causal softmax attention of ``x`` (T, d), ``q`` and ``k`` normed
    over their whole projections, in blocks of queries."""
    t = x.shape[0]
    eps = float(model["rms_norm_eps"])
    q, k, v = (_mm("td,dhk->thk", x, blk[n], quant)
               for n in ("wq", "wk", "wv"))
    heads, dh = q.shape[1:]
    q = rms_norm(blk["q_norm"], q.reshape(t, -1), eps).reshape(q.shape)
    k = rms_norm(blk["k_norm"], k.reshape(t, -1), eps).reshape(k.shape)
    group = heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    at = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        keep = at[None, :hi] <= at[lo:hi, None]
        s = _mm("qhk,shk->hqs", q[lo:hi], k[:hi], quant) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        outs.append(_mm("hqs,shk->qhk", w, v[:hi], quant))
    return _mm("thk,hkd->td", jnp.concatenate(outs), blk["wo"], quant)


def feed_forward(p, h, quant=None):
    a = jax.nn.silu(_mm("td,df->tf", h, p["gate"], quant)) \
        * _mm("td,df->tf", h, p["up"], quant)
    return _mm("tf,fd->td", a, p["down"], quant)


class _Static(dict):
    """The configuration's keys a layer reads, hashable for ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


_LAYER_KEYS = ("linear_num_key_heads", "linear_key_head_dim",
               "linear_conv_kernel_dim", "linear_allow_neg_eigval",
               "rms_norm_eps")


@functools.partial(jax.jit, static_argnames=("kind", "model", "quant"))
def layer(blk, x, *, kind, model, quant=None):
    """One layer over ``x`` (T, d).  Jitted by kind, so that the layers of
    one kind share one compilation."""
    eps = float(model["rms_norm_eps"])
    if kind == "linear":
        out = linear_attention(blk["gdn"], x, model, quant)
    else:
        out = full_attention(blk, x, model, quant)
    x = x + rms_norm(blk["post_ln1"], out, eps)
    return x + rms_norm(blk["post_ln2"],
                        feed_forward(blk["mlp"], x, quant), eps)


def hidden(params, model, ids, quant=None):
    """(T,) int ids -> (T, d) float32: the final RMSNorm's output."""
    x = params["embed"]["table"][ids].astype(jnp.float32)
    static = _Static({k: model[k] for k in _LAYER_KEYS})
    for kind, blk in zip(layer_kinds(model), params["blocks"]):
        x = layer(blk, x, kind=kind, model=static, quant=quant)
    return rms_norm(params["ln_f"], x, float(model["rms_norm_eps"]))


def logits_of(params, x, quant=None):
    """(N, d) -> (N, vocab) float32 through the untied head, whole."""
    return _mm("nd,dv->nv", x, params["head"]["w"], quant)


def forward(params, model, ids, quant=None):
    """(T,) int ids -> (T, vocab) float32 logits, causal."""
    return logits_of(params, hidden(params, model, ids, quant), quant)


# -- how far the served replies lie from it ------------------------------------------


@functools.partial(jax.jit, static_argnames=("quant",))
def served_view(params, x, ids, quant=None):
    """What the server would say of the positions ``x`` (N, d) at the ids
    it served (N, K): the logits at those ids, the logsumexp and the
    logits' standard deviation, the vocabulary taken ``VOCAB_BLOCKS``
    blocks at a time (the head upcast whole is 1.5 GB at the published
    sizes)."""
    head = params["head"]["w"]
    d, vocab = head.shape
    if vocab % VOCAB_BLOCKS:
        raise ValueError(f"vocabulary {vocab} not in {VOCAB_BLOCKS} blocks")
    x = x.astype(jnp.float32)
    if quant == "int8":
        # one scale for the whole head, whichever block a column is in
        scale = jnp.max(jnp.abs(head.astype(jnp.float32))) / 127.0
        x = _fake_int8(x)

        def cols(w):
            return jnp.round(w.astype(jnp.float32) / scale) * scale
    else:
        def cols(w):
            return w.astype(jnp.float32)

    def block(carry, part):
        top, total, sq = carry
        logits = jnp.einsum("nd,dv->nv", x, cols(part), precision=HIGHEST)
        new = jnp.maximum(top, logits.max(-1))
        total = total * jnp.exp(top - new) + jnp.exp(
            logits - new[:, None]).sum(-1)
        return (new, total, sq + jnp.stack(
            [logits.sum(-1), (logits * logits).sum(-1)])), None

    n = x.shape[0]
    (top, total, sums), _ = jax.lax.scan(
        block, (jnp.full((n,), -jnp.inf), jnp.zeros((n,)),
                jnp.zeros((2, n))),
        jnp.moveaxis(head.reshape(d, VOCAB_BLOCKS, vocab // VOCAB_BLOCKS),
                     1, 0))
    mean = sums[0] / vocab
    std = jnp.sqrt(jnp.maximum(sums[1] / vocab - mean * mean, 0.0))
    at_ids = jnp.einsum("nd,dnk->nk", x, cols(jnp.take(head, ids, axis=1)),
                        precision=HIGHEST)
    return at_ids, top + jnp.log(total), std
