"""Plain float32 reference of the `phi4flash` decoder-hybrid-decoder model
(arXiv:2507.06607), and the weights.

Straightforward ``jax.numpy`` following the layer equations: no cache, no
batching, no kernels; every matrix product in float32 at
``Precision.HIGHEST``, the bfloat16 weights upcast inside each product; the
state-space recurrence a plain ``lax.scan`` over positions; attention as
explicit masked softmaxes over blocks of queries; the head over the
positions asked for only, and over the vocabulary in blocks of ids.  It
imports nothing of the program (``blendjax``): the weights come from
:func:`make_params` here, which the program and the reference are both
given.

A model is described by the published (Hugging Face) keys of its
configuration file, with the state-space sizes ``mamba_d_state``,
``mamba_d_conv``, ``mamba_expand`` and ``mamba_dt_rank`` beside them (the
family's convention; ``assumed`` in the file).  ``d`` is ``hidden_size``,
``di = mamba_expand * d``, ``n = mamba_d_state``.  Every layer ``l``::

    x <- x + Mixer_l(LN1_l(x));   x <- x + (up * silu(gate)) W_down
    logits = LN_f(x) E^T                     (LayerNorm, eps layer_norm_eps)

The mixer by layer (``flops_phi4flash.layer_kinds``, the benchmark's copy
of the rule): in the first half every ``mb_per_layer``-th layer is
state-space and the others window attention
(the last ``sliding_window`` positions, itself included); the second half
opens with a state-space layer, whose scan output ``m`` is the memory of
all that follows, and a full-attention layer, whose keys and values the
cross layers read; then gated memory units and cross-attention alternate.

- State-space: ``[u | z] = x W_in``; ``u <- silu(conv1d_causal(u))``;
  ``[r | B | C] = u W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t``; ``y_t =
  h_t C_t + D u_t``; out ``= (y silu(z)) W_out``; ``m = y``.
- Gated memory unit: out ``= (m silu(x W_g)) W_o``.
- Differential attention: heads pair by adjacency, query pair ``j`` over
  K/V pair ``j // (H / Hkv)``; ``a_s = softmax(q_s k_s^T / sqrt(Dh)) [v1 |
  v2]``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``, ``lam_init =
  0.8 - 0.6 exp(-0.3 l)``, ``o = (1 - lam_init) RMSNorm(a1 - lam a2; 1e-5)``.
  A cross layer computes ``q`` only, over the full layer's ``k, v``.

The weights' layout is the program's (``blendjax.models.seqformer``
documents it): channels minor in the state-space leaves (``conv_w (d_conv,
di)``, ``a_log (n, di)``), the feed-forward as ``gate`` / ``up`` / ``down``
(a column partition of the published fused ``gate_up``).

``quant="int8"`` is the control: the same mathematics with both operands of
every matrix product rounded to 8 bits (per tensor, symmetric), the nearest
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.flops_phi4flash import layer_kinds
from chipbench.reference import _fake_int8, seed_key
from chipbench.reference_sarvam import reply_gaps  # noqa: F401 (re-export)

HIGHEST = jax.lax.Precision.HIGHEST
SUBLN_EPS = 1e-5
Q_BLOCK = 256      # queries per block of attention scores
VOCAB_BLOCKS = 8   # the head runs over the vocabulary in this many blocks


def lam_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- the weights ------------------------------------------------------------------


def leaf_shapes(model):
    """``(path, shape, scale, kind)`` of every leaf, in the order they are
    seeded.  ``kind``: ``normal`` (scale is the standard deviation),
    ``norm`` (1 + 0.02 normal), ``bias`` (0.02 normal), ``f32`` (normal,
    kept in float32: the differential vectors), and Mamba's published
    initialisation ``dt_bias`` (the inverse softplus of steps log-uniform
    in 1e-3 .. 1e-1, float32), ``a_log`` (``log(1 .. n)`` down the state
    axis, float32) and ``ones`` (float32)."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv, dh = model["num_key_value_heads"], model["hidden_size"] // heads
    f, vocab = model["intermediate_size"], model["vocab_size"]
    di, n = model["mamba_expand"] * d, model["mamba_d_state"]
    conv, rank = model["mamba_d_conv"], model["mamba_dt_rank"]

    def ln(at):
        return [(at + ("scale",), (d,), 0.0, "norm"),
                (at + ("bias",), (d,), 0.0, "bias")]

    out = [(("embed", "table"), (vocab, d), 1.0, "normal")]
    for i, kind in enumerate(layer_kinds(model)):
        blk = ("blocks", i)
        out += ln(blk + ("ln1",))
        if kind == "ssm":
            s = blk + ("ssm",)
            out += [
                (s + ("in_proj",), (d, 2 * di), d ** -0.5, "normal"),
                (s + ("conv_w",), (conv, di), conv ** -0.5, "normal"),
                (s + ("conv_b",), (di,), 0.0, "bias"),
                (s + ("x_proj",), (di, rank + 2 * n), di ** -0.5, "normal"),
                (s + ("dt_w",), (rank, di), rank ** -0.5, "normal"),
                (s + ("dt_b",), (di,), 0.0, "dt_bias"),
                (s + ("a_log",), (n, di), 0.0, "a_log"),
                (s + ("d_skip",), (di,), 0.0, "ones"),
                (s + ("out_proj",), (di, d), di ** -0.5, "normal"),
            ]
        elif kind == "gmu":
            out += [(blk + ("gmu", "wg"), (d, di), d ** -0.5, "normal"),
                    (blk + ("gmu", "wo"), (di, d), di ** -0.5, "normal")]
        else:
            out.append((blk + ("wq",), (d, heads, dh), d ** -0.5, "normal"))
            if kind != "cross":
                out += [(blk + ("wk",), (d, kv, dh), d ** -0.5, "normal"),
                        (blk + ("wv",), (d, kv, dh), d ** -0.5, "normal")]
            out.append((blk + ("wo",), (heads, dh, d), (heads * dh) ** -0.5,
                        "normal"))
            out += [(blk + ("diff", name), (dh,), 0.1, "f32")
                    for name in ("lq1", "lk1", "lq2", "lk2")]
            out.append((blk + ("diff", "subln", "scale"), (2 * dh,), 0.0,
                        "norm"))
        out += ln(blk + ("ln2",))
        out += [(blk + ("mlp", "gate"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "up"), (d, f), d ** -0.5, "normal"),
                (blk + ("mlp", "down"), (f, d), f ** -0.5, "normal")]
    return out + ln(("ln_f",))


@functools.partial(jax.jit, static_argnames=("shape", "scale", "kind",
                                             "dtype"))
def _leaf(key, *, shape, scale, kind, dtype):
    f32 = jnp.float32
    if kind == "ones":
        return jnp.ones(shape, f32)
    if kind == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], shape)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    x = jax.random.normal(key, shape, f32)
    if kind == "norm":
        return (1.0 + 0.02 * x).astype(dtype)
    if kind == "bias":
        return (0.02 * x).astype(dtype)
    return (x * scale).astype(f32 if kind == "f32" else dtype)


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


def layer_norm(p, x, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def state_space(p, x, quant=None):
    """``x`` (T, d) -> ``(out (T, d), y (T, di))`` from an empty state."""
    f32 = jnp.float32
    t = x.shape[0]
    conv, di = p["conv_w"].shape
    n, rank = p["a_log"].shape[0], p["dt_w"].shape[0]
    uz = _mm("td,de->te", x, p["in_proj"], quant)
    u, z = uz[:, :di], uz[:, di:]
    padded = jnp.concatenate([jnp.zeros((conv - 1, di), f32), u])
    u = p["conv_b"].astype(f32) + sum(
        padded[k:k + t] * p["conv_w"][k].astype(f32) for k in range(conv))
    u = jax.nn.silu(u)
    rbc = _mm("te,er->tr", u, p["x_proj"], quant)
    dt = jax.nn.softplus(_mm("tr,re->te", rbc[:, :rank], p["dt_w"], quant)
                         + p["dt_b"].astype(f32))
    b_sel, c_sel = rbc[:, rank:rank + n], rbc[:, rank + n:]
    a = -jnp.exp(p["a_log"].astype(f32))  # (n, di)

    def step(h, at):
        dt_t, u_t, b_t, c_t = at
        h = jnp.exp(dt_t[None] * a) * h + (dt_t * u_t)[None] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, di), f32), (dt, u, b_sel, c_sel))
    y = y + p["d_skip"].astype(f32) * u
    return _mm("te,ed->td", y * jax.nn.silu(z), p["out_proj"], quant), y


def gated_memory(p, x, memory, quant=None):
    gate = jax.nn.silu(_mm("td,de->te", x, p["wg"], quant))
    return _mm("te,ed->td", memory * gate, p["wo"], quant)


def keys_values(blk, x, quant=None):
    """``(k, v)``, each (T, Hkv, Dh)."""
    return (_mm("td,dhk->thk", x, blk["wk"], quant),
            _mm("td,dhk->thk", x, blk["wv"], quant))


def diff_attention(blk, x, k, v, lam_init_l, window, quant=None):
    """Differential attention of normed ``x`` (T, d) over ``k``, ``v``
    (T, Hkv, Dh) of the same positions; ``window`` 0 is no window."""
    f32 = jnp.float32
    t = x.shape[0]
    q = _mm("td,dhk->thk", x, blk["wq"], quant)
    heads, dh = q.shape[1:]
    group = heads // k.shape[1]
    q1, q2 = q[:, 0::2], q[:, 1::2]                      # (T, H/2, Dh)
    k1, k2 = (jnp.repeat(k[:, s::2], group, axis=1) for s in (0, 1))
    v12 = jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1)  # (T, Hkv/2, 2 Dh)
    v12 = jnp.repeat(v12, group, axis=1)
    d = blk["diff"]
    lam = (jnp.exp(jnp.sum(d["lq1"].astype(f32) * d["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(d["lq2"].astype(f32) * d["lk2"].astype(f32)))
           + lam_init_l)
    at = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        keep = at[None, :hi] <= at[lo:hi, None]
        keep &= (window == 0) | (at[None, :hi] > at[lo:hi, None] - window)

        def one(qs, ks):
            s = _mm("qhk,shk->hqs", qs[lo:hi], ks[:hi], quant) * dh ** -0.5
            w = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
            return _mm("hqs,she->qhe", w, v12[:hi], quant)

        a = one(q1, k1) - lam * one(q2, k2)              # (q, H/2, 2 Dh)
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + SUBLN_EPS)
        outs.append((1.0 - lam_init_l) * a
                    * d["subln"]["scale"].astype(f32))
    o = jnp.concatenate(outs).reshape(t, heads, dh)
    return _mm("thk,hkd->td", o, blk["wo"], quant)


def feed_forward(p, h, quant=None):
    a = jax.nn.silu(_mm("td,df->tf", h, p["gate"], quant)) \
        * _mm("td,df->tf", h, p["up"], quant)
    return _mm("tf,fd->td", a, p["down"], quant)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "window",
                                             "quant"))
def layer(blk, x, memory, kv, lam_init_l, *, kind, eps, window, quant=None):
    """One layer over ``x`` (T, d): ``(x, memory, kv)`` handed on.  Jitted
    by kind, so that the layers of one kind share one compilation
    (``lam_init_l`` is an operand)."""
    h = layer_norm(blk["ln1"], x, eps)
    if kind == "ssm":
        out, memory = state_space(blk["ssm"], h, quant)
    elif kind == "gmu":
        out = gated_memory(blk["gmu"], h, memory, quant)
    else:
        if kind != "cross":
            kv = keys_values(blk, h, quant)
        out = diff_attention(blk, h, *kv, lam_init_l,
                             window if kind == "window" else 0, quant)
    x = x + out
    x = x + feed_forward(blk["mlp"], layer_norm(blk["ln2"], x, eps), quant)
    return x, memory, kv


def hidden(params, model, ids, quant=None):
    """(T,) int ids -> (T, d) float32: the final LayerNorm's output."""
    x = params["embed"]["table"][ids].astype(jnp.float32)
    eps = float(model["layer_norm_eps"])
    memory = kv = None
    for i, (kind, blk) in enumerate(zip(layer_kinds(model),
                                        params["blocks"])):
        x, memory, kv = layer(
            blk, x, memory, kv, jnp.float32(lam_init(i)), kind=kind, eps=eps,
            window=int(model["sliding_window"]), quant=quant)
    return layer_norm(params["ln_f"], x, eps)


def logits_of(params, x, quant=None):
    """(N, d) -> (N, vocab) float32 through the tied embedding, whole."""
    return _mm("nd,vd->nv", x, params["embed"]["table"], quant)


def forward(params, model, ids, quant=None):
    """(T,) int ids -> (T, vocab) float32 logits, causal."""
    return logits_of(params, hidden(params, model, ids, quant), quant)


# -- how far the served replies lie from it ------------------------------------------


@functools.partial(jax.jit, static_argnames=("quant",))
def served_view(params, x, ids, quant=None):
    """What the server would say of the positions ``x`` (N, d) at the ids
    it served (N, K): the logits at those ids, the logsumexp and the
    logits' standard deviation, the vocabulary taken ``VOCAB_BLOCKS``
    blocks at a time (the table upcast whole is 2 GB at the published
    sizes)."""
    table = params["embed"]["table"]
    vocab = table.shape[0]
    if vocab % VOCAB_BLOCKS:
        raise ValueError(f"vocabulary {vocab} not in {VOCAB_BLOCKS} blocks")
    x = x.astype(jnp.float32)
    if quant == "int8":
        # one scale for the whole table, whichever block a row is in
        scale = jnp.max(jnp.abs(table.astype(jnp.float32))) / 127.0
        x = _fake_int8(x)

        def rows(t):
            return jnp.round(t.astype(jnp.float32) / scale) * scale
    else:
        def rows(t):
            return t.astype(jnp.float32)

    def block(carry, part):
        top, total, sq = carry
        logits = jnp.einsum("nd,vd->nv", x, rows(part), precision=HIGHEST)
        new = jnp.maximum(top, logits.max(-1))
        total = total * jnp.exp(top - new) + jnp.exp(
            logits - new[:, None]).sum(-1)
        return (new, total, sq + jnp.stack(
            [logits.sum(-1), (logits * logits).sum(-1)])), None

    n = x.shape[0]
    (top, total, sums), _ = jax.lax.scan(
        block, (jnp.full((n,), -jnp.inf), jnp.zeros((n,)),
                jnp.zeros((2, n))),
        table.reshape(VOCAB_BLOCKS, vocab // VOCAB_BLOCKS, -1))
    mean = sums[0] / vocab
    std = jnp.sqrt(jnp.maximum(sums[1] / vocab - mean * mean, 0.0))
    at_ids = jnp.einsum("nd,nkd->nk", x, rows(table[ids]), precision=HIGHEST)
    return at_ids, top + jnp.log(total), std
