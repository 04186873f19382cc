"""Plain float32 reference of the `sarvam_mla` block, and the weights.

Straightforward ``jax.numpy`` following the published layer equations: no
cache, no batching, no kernels, no blocks; every matrix product runs at
``Precision.HIGHEST`` in float32.  The weights are kept as they were seeded
(bfloat16 on the chip) and upcast inside each product, expert by expert, so
that the reference fits beside them.  It imports nothing of the program
(``blendjax``): the weights come from :func:`make_params` here, which both
the program and the reference are given, in the layout
``blendjax.models.seqformer.init_token_model`` documents.

A model is described by the published (Hugging Face) keys of its
configuration file, with ``num_experts_held`` and ``held_first`` beside
``num_experts``: the reference routes over all ``num_experts`` and computes
the experts ``[held_first, held_first + num_experts_held)`` and the shared
one, the same share the program holds.

The equations (``x`` is ``hidden_size`` wide; ``h = RMSNorm(x)``):

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + 1e-6) * g``; pre-norm residual
  blocks, a final RMSNorm, an untied head.
- MLA: ``q = h W_q`` as heads of ``[q_nope | q_pe]``; ``[c | k_pe] = h
  W_dkv``; ``c = RMSNorm(c)``; ``q_pe``, ``k_pe`` rotated by YaRN rope
  (``k_pe`` one vector for all heads); ``[k_nope_h | v_h] = c W_ukv``;
  ``s = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale``, causal softmax,
  ``out = concat_h(sum p v_h) W_o``; ``scale = q_head_dim^-0.5 * m^2``,
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- Experts: ``E(h) = W_down(silu(W_gate h) * (W_up h))``, no biases.
- Router, float32: ``s = sigmoid(h W_r)``; ``sel = top_k(s + b)``; ``g_e =
  scaling * s_e / sum_sel s``; ``y = sum_{e in sel, e held} g_e E_e(h) +
  E_shared(h)``; no capacity, nothing dropped.

Departures from the published code, each immaterial on seeded weights or
stated under ``assumed`` in the configuration file: rope pairs are taken
half-split (the interleaved form is a column permutation of ``W_q`` and
``W_dkv``); ``use_qk_norm`` is read as the RMSNorm on the latent ``c``; the
score function is sigmoid with the selected weights normalised.

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

from chipbench.reference import _fake_int8, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6


# -- the weights ------------------------------------------------------------------


def leaf_shapes(model):
    """``(path, shape, scale, kind)`` of every leaf, in the order they are
    seeded: ``kind`` is ``normal`` (scale is the standard deviation),
    ``norm`` (1 + 0.02 normal) or ``f32`` (normal, kept in float32: the
    router)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope, v = model["qk_rope_head_dim"], model["v_head_dim"]
    vocab, held = model["vocab_size"], model["num_experts_held"]
    out = [(("embed", "table"), (vocab, d), 1.0, "normal")]
    for i in range(model["num_hidden_layers"]):
        blk = ("blocks", i)
        out += [
            (blk + ("ln1", "scale"), (d,), 0.0, "norm"),
            (blk + ("mla", "wq"), (d, h, nope + rope), d ** -0.5, "normal"),
            (blk + ("mla", "wdkv"), (d, rank + rope), d ** -0.5, "normal"),
            (blk + ("mla", "c_norm", "scale"), (rank,), 0.0, "norm"),
            (blk + ("mla", "wukv"), (rank, h, nope + v), rank ** -0.5,
             "normal"),
            (blk + ("mla", "wo"), (h, v, d), (h * v) ** -0.5, "normal"),
            (blk + ("ln2", "scale"), (d,), 0.0, "norm"),
        ]
        if i < model["first_k_dense_replace"]:
            f = model["intermediate_size"]
            out += [(blk + ("mlp", "gate"), (d, f), d ** -0.5, "normal"),
                    (blk + ("mlp", "up"), (d, f), d ** -0.5, "normal"),
                    (blk + ("mlp", "down"), (f, d), f ** -0.5, "normal")]
            continue
        f, moe = model["moe_intermediate_size"], blk + ("moe",)
        out += [
            (moe + ("router", "w"), (d, model["num_experts"]), d ** -0.5,
             "f32"),
            # small against the spread of the scores, so that load stays
            # balanced (which is what a deployment's bias is trained for)
            # while it still decides near ties
            (moe + ("router", "bias"), (model["num_experts"],), 0.005, "f32"),
            (moe + ("gate",), (held, d, f), d ** -0.5, "normal"),
            (moe + ("up",), (held, d, f), d ** -0.5, "normal"),
            (moe + ("down",), (held, f, d), f ** -0.5, "normal"),
        ]
        if model.get("num_shared_experts"):
            sh = moe + ("shared",)
            out += [(sh + ("gate",), (d, f), d ** -0.5, "normal"),
                    (sh + ("up",), (d, f), d ** -0.5, "normal"),
                    (sh + ("down",), (f, d), f ** -0.5, "normal")]
    out += [(("ln_f", "scale"), (d,), 0.0, "norm"),
            (("head", "w"), (d, vocab), d ** -0.5, "normal")]
    return out


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
    ``spec`` / ``route`` are the caller's to add."""
    n_blocks = model["num_hidden_layers"]
    tree = {"blocks": [{} for _ in range(n_blocks)]}
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


def rms_norm(g, x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * g.astype(jnp.float32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model):
    """Inverse frequencies over the rope dimensions: ``base^(-2i/dim)``,
    and under ``rope_scaling`` (``deepseek_yarn``) blended with that over
    ``factor`` by the linear ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow``."""
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ys = model.get("rope_scaling")
    if not ys:
        return plain

    def correction_dim(rotations):
        return dim * math.log(ys["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(ys["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain * (1 - ramp) + plain / ys["factor"] * ramp


def rope_tables(model, t):
    ang = np.arange(t)[:, None] * yarn_inv_freq(model)[None]
    ys = model.get("rope_scaling")
    m = 1.0 if not ys else (yarn_mscale(ys["factor"], ys["mscale"])
                            / yarn_mscale(ys["factor"],
                                          ys["mscale_all_dim"]))
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def softmax_scale(model):
    ys = model.get("rope_scaling")
    m = 1.0 if not ys else yarn_mscale(ys["factor"], ys["mscale_all_dim"])
    return (model["qk_nope_head_dim"]
            + model["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, cos, sin):
    """Half-split rotation of (T, ..., rope) by (T, rope/2) tables."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def mla(p, model, h, quant=None):
    """Latent attention over one sequence ``h`` (T, d), expanded."""
    t = h.shape[0]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    cos, sin = rope_tables(model, t)
    q = _mm("td,dhk->thk", h, p["wq"], quant)
    ckpe = _mm("td,dk->tk", h, p["wdkv"], quant)
    c = rms_norm(p["c_norm"]["scale"], ckpe[:, :rank])
    k_pe = _rotate(ckpe[:, rank:], cos, sin)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], cos, sin)
    kv = _mm("tr,rhk->thk", c, p["wukv"], quant)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (_mm("qhk,shk->hqs", q_nope, k_nope, quant)
         + _mm("qhk,sk->hqs", q_pe, k_pe, quant)) * softmax_scale(model)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = _mm("hqs,shv->qhv", jax.nn.softmax(s, -1), v, quant)
    return _mm("qhv,hvd->qd", o, p["wo"], quant)


def expert(gate, up, down, h, quant=None):
    """``down(silu(gate h) * (up h))``."""
    a = jax.nn.silu(_mm("td,df->tf", h, gate, quant)) \
        * _mm("td,df->tf", h, up, quant)
    return _mm("tf,fd->td", a, down, quant)


def route(p, model, h, quant=None):
    """(T, num_experts) weights: ``scaling * s_e / sum_sel s`` on the
    ``top_k`` of ``s + bias``, 0 elsewhere."""
    s = jax.nn.sigmoid(_mm("td,de->te", h, p["w"], quant))
    _, sel = jax.lax.top_k(s + p["bias"], model["num_experts_per_tok"])
    chosen = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], sel].set(1.0)
    w = s * chosen
    return model["routed_scaling_factor"] * w / w.sum(-1, keepdims=True)


def moe(p, model, h, quant=None, shared=True):
    """The held share of the expert layer: every held expert over every
    token, one expert at a time, under its weight (0 for a token that did
    not choose it), plus the shared expert."""
    first = model.get("held_first", 0)
    held = p["gate"].shape[0]
    g = route(p["router"], model, h, quant)[:, first:first + held]

    def one(y, e):
        gate, up, down, g_e = e
        return y + g_e[:, None] * expert(gate, up, down, h, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                        (p["gate"], p["up"], p["down"], g.T))
    if shared and "shared" in p:
        y = y + expert(p["shared"]["gate"], p["shared"]["up"],
                       p["shared"]["down"], h, quant)
    return y


def forward(params, model, ids, quant=None, shared=True):
    """(T,) int ids -> (T, vocab) float32 logits, causal."""
    x = params["embed"]["table"][ids].astype(jnp.float32)
    for blk in params["blocks"]:
        x = x + mla(blk["mla"], model, rms_norm(blk["ln1"]["scale"], x),
                    quant)
        h = rms_norm(blk["ln2"]["scale"], x)
        if "moe" in blk:
            x = x + moe(blk["moe"], model, h, quant, shared)
        else:
            x = x + expert(blk["mlp"]["gate"], blk["mlp"]["up"],
                           blk["mlp"]["down"], h, quant)
    x = rms_norm(params["ln_f"]["scale"], x)
    return _mm("td,dv->tv", x, params["head"]["w"], quant)


# -- how far the served replies lie from it ------------------------------------------


def served_view(logits, ids):
    """What the server would say of float32 ``logits`` (N, V) at the ids
    it served (N, K): those logits, and the logsumexp."""
    return (jnp.take_along_axis(logits, ids, axis=-1),
            jax.nn.logsumexp(logits, axis=-1))


def reply_gaps(replies, ref_top, ref_lse, ref_std):
    """Served reply rows (N, 2K + 1: K logits, their K ids, the logsumexp)
    against the reference's logits at those ids and its logsumexp, each
    over the reference logits' standard deviation at that position.  The
    median is the number that tells precisions apart: wherever rounding
    turns a token's eighth and ninth expert round (a near tie among 128
    scores), that token's logits move by a tenth of their spread or more
    in any precision, and such flips set the root mean square and the
    maximum."""
    replies = np.asarray(replies, np.float64)
    k = (replies.shape[1] - 1) // 2
    std = np.asarray(ref_std, np.float64)
    gap = np.abs(replies[:, :k] - np.asarray(ref_top, np.float64)) \
        / std[:, None]
    lse = np.abs(replies[:, -1] - np.asarray(ref_lse, np.float64)) / std
    return {"logit_gap_p50": float(np.median(gap)),
            "logit_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "logit_gap_max": float(gap.max()),
            "lse_gap_max": float(lse.max())}
