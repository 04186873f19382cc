"""Plain float32 reference of the SeqFormer world model, and the weights.

Straightforward ``jax.numpy``: LayerNorm, multi-head causal attention with a
learned position table, tanh-GELU MLP, a linear head, mean-squared error, and
Adam written out.  No kernel, no cache, no batching tricks; every matrix
product runs at ``Precision.HIGHEST``.  It imports nothing of the program
(``blendjax``) and takes nothing the program has made: the weights come from
:func:`make_params` here, which both the program and the reference are given.

``quant="int8"`` is the training control: the same mathematics with both
operands of every matrix product rounded to 8 bits (per tensor, symmetric),
the nearest precision below the bfloat16 the train configuration states.
``quant="bf16_3x"`` is the serving control: every product in three
bfloat16 passes (``Precision.HIGH``), the nearest precision below the
float32 at ``highest`` that the serve configuration states, written out so
that it reads the same on any backend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6


def seed_key(seed):
    """A PRNG key from any non-negative whole seed (beyond 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=(
    "obs_dim", "d_model", "n_heads", "n_layers", "d_ff", "max_len"))
def _make_params(key, *, obs_dim, d_model, n_heads, n_layers, d_ff, max_len):
    dh = d_model // n_heads
    keys = iter(jax.random.split(key, 8 + 16 * n_layers))

    def normal(shape, scale):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def dense(d_in, d_out):
        return {"w": normal((d_in, d_out), math.sqrt(2.0 / d_in)),
                "b": normal((d_out,), 0.02)}

    def ln():
        return {"scale": 1.0 + normal((d_model,), 0.02),
                "bias": normal((d_model,), 0.02)}

    s = math.sqrt(1.0 / d_model)
    blocks = []
    for _ in range(n_layers):
        blocks.append({
            "ln1": ln(),
            "wq": {"w": normal((d_model, n_heads, dh), s),
                   "b": normal((n_heads, dh), 0.02)},
            "wk": {"w": normal((d_model, n_heads, dh), s),
                   "b": normal((n_heads, dh), 0.02)},
            "wv": {"w": normal((d_model, n_heads, dh), s),
                   "b": normal((n_heads, dh), 0.02)},
            "wo": {"w": normal((n_heads, dh, d_model), s),
                   "b": normal((d_model,), 0.02)},
            "ln2": ln(),
            "mlp": {"fc": dense(d_model, d_ff), "proj": dense(d_ff, d_model)},
        })
    return {
        "embed": dense(obs_dim, d_model),
        "pos": normal((max_len, d_model), 0.02),
        "blocks": blocks,
        "ln_f": ln(),
        "head": dense(d_model, obs_dim),
    }


def make_params(model, seed):
    """The float32 parameter tree (the layout ``blendjax.models.seqformer``
    documents), made on the device in one jitted call from the seed."""
    return _make_params(
        seed_key(seed), obs_dim=model["obs_dim"], d_model=model["d_model"],
        n_heads=model["n_heads"], n_layers=model["n_layers"],
        d_ff=model["d_ff"], max_len=model["max_len"])


# -- the model -----------------------------------------------------------------


def _fake_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def _round_bf16(x):
    # not astype there and back: the compiler may drop such a pair as
    # "excess precision" (on the chip it did, and x - hi came out 0)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split_bf16(x):
    """x ~ hi + lo, both exactly representable in bfloat16."""
    hi = _round_bf16(x)
    return hi, _round_bf16(x - hi)


def _mm(eq, a, b, quant):
    def dot(x, y):
        return jnp.einsum(eq, x, y, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    if quant is None:
        return dot(a, b)
    if quant == "int8":
        return dot(_fake_int8(a), _fake_int8(b))
    if quant == "bf16_3x":  # hi*hi + hi*lo + lo*hi: three bfloat16 passes
        (a_hi, a_lo), (b_hi, b_lo) = _split_bf16(a), _split_bf16(b)
        return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)
    raise ValueError(f"unknown quant {quant!r}")


def _ln(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, obs, quant=None):
    """(B, T, obs_dim) observations -> (B, T, obs_dim) next-observation
    predictions, causal."""
    t = obs.shape[1]
    x = _mm("btd,de->bte", obs, params["embed"]["w"], quant)
    x = x + params["embed"]["b"] + params["pos"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for blk in params["blocks"]:
        h = _ln(blk["ln1"], x)
        q, k, v = (_mm("btd,dhk->bthk", h, blk[n]["w"], quant) + blk[n]["b"]
                   for n in ("wq", "wk", "wv"))
        s = _mm("bqhk,bshk->bhqs", q, k, quant) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        a = _mm("bhqs,bshk->bqhk", p, v, quant)
        x = x + _mm("bqhk,hkd->bqd", a, blk["wo"]["w"], quant) \
            + blk["wo"]["b"]
        h = _ln(blk["ln2"], x)
        h = _gelu(_mm("btd,df->btf", h, blk["mlp"]["fc"]["w"], quant)
                  + blk["mlp"]["fc"]["b"])
        x = x + _mm("btf,fd->btd", h, blk["mlp"]["proj"]["w"], quant) \
            + blk["mlp"]["proj"]["b"]
    x = _ln(params["ln_f"], x)
    return _mm("btd,de->bte", x, params["head"]["w"], quant) \
        + params["head"]["b"]


def sum_sq_error(params, episode, quant=None):
    """Sum of squared next-observation errors over (B, T+1, D) episodes."""
    pred = forward(params, episode[:, :-1], quant)
    return jnp.sum((pred - episode[:, 1:]) ** 2)


# -- training, written out -------------------------------------------------------


def leaf_norms(tree):
    """The Euclidean norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def delta_norms(new, old):
    return leaf_norms(jax.tree.map(lambda a, b: a - b, new, old))


def train_reference(params, episodes, opt, row_block, quant=None):
    """Follow ``len(episodes)`` Adam steps from ``params`` (which is kept).

    ``episodes`` is a list of (B, T+1, D) arrays, one per step.  The
    gradient of the mean-squared error is summed over blocks of
    ``row_block`` rows so that float32 activations fit beside the state.
    Returns the loss of each step, the per-leaf norm of the first gradient
    and the per-leaf norm of the parameters' change after the last step.
    """
    lr, b1, b2, eps = (opt["learning_rate"], opt["b1"], opt["b2"],
                       opt["eps"])

    @jax.jit
    def block_grad(p, ep, denom):
        return jax.value_and_grad(
            lambda p_: sum_sq_error(p_, ep, quant) / denom)(p)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def tree_add(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, t):
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        mhat = 1.0 / (1 - b1 ** t)
        vhat = 1.0 / (1 - b2 ** t)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (m_ * mhat)
            / (jnp.sqrt(v_ * vhat) + eps), p, m, v)
        return p, m, v

    p = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for step, ep in enumerate(episodes, start=1):
        ep = jnp.asarray(ep, jnp.float32)
        denom = float(ep.shape[0] * (ep.shape[1] - 1) * ep.shape[2])
        loss, grads = 0.0, None
        for lo in range(0, ep.shape[0], row_block):
            part, g = block_grad(p, ep[lo:lo + row_block], denom)
            loss = loss + part
            grads = g if grads is None else tree_add(grads, g)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        p, m, v = adam(p, m, v, grads, jnp.float32(step))
    return {
        "losses": np.asarray(jnp.stack(losses), np.float64),
        "grad_norms": np.asarray(grad_norms, np.float64),
        "delta_norms": np.asarray(delta_norms(p, params), np.float64),
    }


# -- how far two sets of readings lie apart ------------------------------------------


def worst_leaf_gap(got, ref, counted=None):
    """The widest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  ``counted`` masks the leaves that count."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(got - ref) / scale
    if counted is not None:
        gap = np.where(counted, gap, 0.0)
    worst = int(np.argmax(gap))
    return float(gap[worst]), worst


def moved_leaves(ref_grad_norms):
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's.  The others (a key's bias under
    softmax) move under Adam by round-off alone and are left out of the
    parameters' change."""
    ref = np.asarray(ref_grad_norms, np.float64)
    return ref >= 1e-3 * np.median(ref)


def prediction_gaps(got, ref):
    """Served predictions (N, D) against the reference's: the widest and the
    root-mean-square relative distance of a prediction vector."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    rel = np.linalg.norm(got - ref, axis=-1) / np.maximum(
        np.linalg.norm(ref, axis=-1), 1e-30)
    return float(rel.max()), float(np.sqrt(np.mean(rel ** 2)))
