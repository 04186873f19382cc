"""SeqFormer — temporal transformer over streamed Blender episodes.

The reference has no sequence models (SURVEY.md §5: long-context "absent");
blendjax's episodes — frames, observations, actions streamed out of
Blender — are sequences, and this is the flagship long-context model over
them: a causal transformer world-model that consumes an episode's
observation sequence and predicts the next observation at every step
(the standard self-supervised objective for learned simulators).

TPU-first design decisions:

- plain ``{name: array}`` pytrees (jit/shard/donate-clean, like every
  blendjax model);
- bfloat16 compute on the MXU, float32 params and softmax/layernorm
  accumulation;
- **pluggable attention**: ``apply(..., attn_fn=...)`` accepts any
  ``(q, k, v) -> out`` — pass
  :func:`blendjax.parallel.make_ring_attention` output to run the sequence
  axis sharded over the mesh (ring or Ulysses), nothing to change in the
  model;
- optional **mixture-of-experts MLP** (``n_experts > 0``): expert weights
  stack on a leading axis that shards over an ``'expert'`` mesh axis.
  Two apply-time evaluation modes over the SAME parameters:
  ``moe_impl='dense'`` (soft mixture, every expert evaluated, gate-
  weighted psum over the expert shards) and ``moe_impl='topk'`` (routed
  expert parallelism — top-k gating with capacity factor, static-shaped
  GShard-style dispatch, dropped tokens ride the residual; see
  :mod:`blendjax.models.moe`).

**The block is read off the params.**  Every function here dispatches,
block by block, on what the pytree holds, so one `_forward`, one
`init_cache` and one `decode_step` serve every model and no argument
says which it is: `ln*` with a `bias` is LayerNorm, without one RMSNorm;
`mla` in place of `wq/wk/wv/wo` is latent attention
(:mod:`blendjax.models.mla`: one low-rank cache row a position,
expanded in the forward pass, absorbed in a decode step); an `mlp` with
a `gate` is the gated SiLU MLP; a `moe` with a `route` is the held share
of a routed layer, its scores sigmoid or softmax as the route says
(:func:`blendjax.models.moe.moe_apply_held`);
`embed` with a `table` takes int32 ids, and a `head` without a bias
answers with float32 logits over its vocabulary slice
(:func:`init_token_model`); no `head` at all is the embedding tied.

**Layers of different kinds in one model** (:func:`init_hybrid_model`;
the kinds are :func:`hybrid_layer_kinds`' rule) are read off each block
the same way: an `ssm` entry is a state-space mixer
(:mod:`blendjax.models.mamba`: a recurrent state and a convolution tail
per sequence, no position indexes them), a `gmu` entry a gated memory
unit over the last state-space layer's scan output, a `diff` entry
differential attention (:mod:`blendjax.models.diffattn`) -- over its own
keys and values, a ring of `spec.window` positions or the full length,
or, in a block without `wk`/`wv`, over the keys and values of the last
block that made any (a *cross* layer: eight layers then read one
buffer).  Such a model has no positional encoding.  A `gdn` entry is
gated delta-rule linear attention (:mod:`blendjax.models.deltanet`: a
float32 matrix state a head and three convolution tails per sequence);
an `ssd` entry a Mamba-2 mixer (:mod:`blendjax.models.mamba2`: a float32
matrix state a head with a scalar decay, one convolution tail);
`wq`/`wk`/`wv`/`wo` without a `diff` entry, inside such a model, are plain
softmax attention, with a `q_norm` / `k_norm` RMSNorm where the block
holds them (over each head where its scale is a head wide, else over the
whole projection) and, where the block holds an `attn` entry (its static
:class:`AttnSpec`), a window (a ring of that many positions), a
rotary embedding of its own and a softmax scale; without one, over a
full-length K/V, unrotated, at ``1 / sqrt(Dh)``.  The kinds are then the
configuration's own ``layer_types`` (:func:`init_linear_hybrid_model`,
:func:`init_ssd_hybrid_model`; :func:`describe_token_model`): a model of
window and full attention layers only, with no recurrent block, is such a
model too.

**The norm's place is read off the block too**: `ln1` / `ln2` norm a
sublayer's input (``x + f(ln(x))``), `post_ln1` / `post_ln2` its output
(``x + ln(f(x))``, the Olmo 2 convention).  A model with muP multipliers
holds a static :class:`MupSpec` as ``mup``, at the top (the embedding's
rows times ``embedding``, the logits over ``logits``) and in each block
(each sublayer's output times ``residual`` before its residual add).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from blendjax.models import deltanet, diffattn, mamba, mamba2, mla
from blendjax.models.layers import (
    apply_rope,
    apply_rope_rows,
    dense_apply,
    dense_init,
    gelu,
    rms_norm,
    rope_table,
    scaled_normal,
    yarn_mscale,
)
from blendjax.models.moe import (
    RouteSpec,
    gated_mlp,
    gated_mlp_init,
    held_init,
    moe_apply_held,
    moe_apply_topk,
)
from blendjax.ops.quant import maybe_quantized_einsum
from blendjax.parallel.ring_attention import full_attention


def _dense_mq(p, x, dtype):
    """``dense_apply`` accepting either a float ``{'w', 'b'}`` or an
    int8 ``{'w_q', 'w_scale', 'b'}`` weight dict
    (:func:`blendjax.ops.quant.quantize_seqformer`)."""
    if "w_q" not in p:
        return dense_apply(p, x, dtype=dtype)
    out = maybe_quantized_einsum("...d,df->...f", x, p, dtype)
    return (out + p["b"]).astype(dtype)


def _proj_mq(p, x, eq, dtype):
    """Head-major attention projection with the same float/int8
    dispatch; bias included."""
    out = maybe_quantized_einsum(eq, x, p, dtype)
    b = p["b"].astype(dtype if "w_q" not in p else jnp.float32)
    return (out + b).astype(dtype)


def _wq_head_dim(params):
    wq = params["blocks"][0]["wq"]
    return (wq["w"] if "w" in wq else wq["w_q"]).shape[-1]


def _ln_init(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class NormSpec:
    """A norm's epsilon where it is not this module's 1e-6 (an entry
    ``"spec"`` beside ``scale``)."""

    eps: float


@jax.named_scope("ln")
def _ln_apply(p, x):
    eps = p["spec"].eps if "spec" in p else 1e-6
    if "bias" not in p:
        return rms_norm(p["scale"], x, eps)
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class MupSpec:
    """A model's muP multipliers (an entry ``"mup"`` at the top of the
    params and in each block; absent, every one is 1)."""

    embedding: float = 1.0
    residual: float = 1.0
    logits: float = 1.0


def _pre(blk, name, x):
    """A sublayer's input: normed where the block norms before it."""
    return _ln_apply(blk[name], x) if name in blk else x


def _post(blk, name, y):
    """A sublayer's output: normed where the block norms after it
    (``post_ln1`` / ``post_ln2`` in place of ``ln1`` / ``ln2``), then
    times the block's residual multiplier where it has one."""
    name = "post_" + name
    if name in blk:
        y = _ln_apply(blk[name], y)
    return y * blk["mup"].residual if "mup" in blk else y


def _moe_init(key, n_experts, d, d_ff):
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = jnp.sqrt(2.0 / d)
    s2 = jnp.sqrt(2.0 / d_ff)
    return {
        "gate": dense_init(kg, d, n_experts),
        "w1": jax.random.normal(k1, (n_experts, d, d_ff)) * s1,
        "b1": jnp.zeros((n_experts, d_ff)),
        "w2": jax.random.normal(k2, (n_experts, d_ff, d)) * s2,
        "b2": jnp.zeros((n_experts, d)),
    }


def _moe_apply(p, x, dtype):
    """Soft mixture over all experts (static shapes, expert-sharded psum)."""
    gates = jax.nn.softmax(dense_apply(p["gate"], x, dtype=jnp.float32), axis=-1)
    h = jnp.einsum("btd,edf->betf", x.astype(dtype), p["w1"].astype(dtype))
    h = gelu(h + p["b1"][None, :, None, :].astype(dtype))
    y = jnp.einsum("betf,efd->betd", h, p["w2"].astype(dtype))
    y = y + p["b2"][None, :, None, :].astype(dtype)
    return jnp.einsum("bte,betd->btd", gates.astype(dtype), y)


def _latent(params):
    """Whether the model's attention is latent (``mla`` blocks)."""
    return "mla" in params["blocks"][0]


#: the recurrent mixers, by the block entry that holds one: the module
#: (``state_shapes``, ``mix_sequence``, ``mix_step``, ``STEPS_IN_PLACE``:
#: how many leading entries its step reads and writes in the pool, handed
#: the whole leaf and the stepped rows) and the cache entries
#: it keeps, in ``state_shapes``' order: a float32 state, then what the
#: cache's dtype keeps (convolution tails).  No position indexes them:
#: they are written whole, and zeroed (not masked) when a row is rewound.
_MIXERS = {
    "ssm": (mamba, ("ssm_h", "ssm_tail")),
    "gdn": (deltanet, ("gdn_s", "gdn_tail_q", "gdn_tail_k", "gdn_tail_v")),
    "ssd": (mamba2, ("ssd_s", "ssd_tail")),
}
_RECURRENT = tuple(name for _, names in _MIXERS.values() for name in names)


def _mixer(blk):
    """``(entry, module, cache names)`` of the block's recurrent mixer,
    or None."""
    for kind, (module, names) in _MIXERS.items():
        if kind in blk:
            return kind, module, names
    return None


def _recurrent(params):
    """Whether any block keeps recurrent state: it is zeroed, not
    masked, when a row is rewound."""
    return any(_mixer(blk) for blk in params["blocks"])


def _hybrid(params):
    """Whether the model mixes layer kinds (recurrent blocks, or plain
    attention blocks of described kinds): its cache then holds, block by
    block, rings and full-length keys and values side by side (and the
    recurrent state), and :class:`_HybridStep` steps it."""
    return _recurrent(params) or any("attn" in blk
                                     for blk in params["blocks"])


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """What the shapes of a plain attention block of a model of mixed
    kinds do not say (its entry ``attn``): how many positions a query
    sees, its own included (``window``; None: all of them), its
    rotary embedding (``theta``; None: none): the default rotation, or
    with ``yarn`` (``factor, beta_fast, beta_slow, original_max``) the
    YaRN frequencies, the tables times ``attention_factor``
    (:func:`~blendjax.models.layers.rope_table`), and the softmax's
    ``scale`` (None: ``1 / sqrt(Dh)``)."""

    window: int | None = None
    theta: float | None = None
    yarn: tuple | None = None
    attention_factor: float | None = None
    scale: float | None = None


def _window_of(blk):
    """The positions a query of an attention block sees, or None (all)."""
    if "diff" in blk:
        return blk["diff"]["spec"].window
    return blk["attn"].window if "attn" in blk else None


def _scale_of(blk, dh):
    """The softmax scale of a plain attention block of ``Dh``-wide heads."""
    spec = blk.get("attn")
    return dh ** -0.5 if spec is None or spec.scale is None else spec.scale


def _windows_are_described(hybrid, window):
    if hybrid and window is not None:
        raise ValueError("a model of mixed layer kinds takes its windows "
                         "from its description, layer by layer")


def _embed(params, obs, dtype):
    """Observations through the dense projection, or int ids through the
    table (an id outside it is clipped)."""
    emb = params["embed"]
    if "table" not in emb:
        return _dense_mq(emb, obs.astype(dtype), dtype)
    rows = jnp.take(emb["table"], obs, axis=0, mode="clip")
    if "mup" in params:
        rows = rows.astype(jnp.float32) * params["mup"].embedding
    return rows.astype(dtype)


@jax.named_scope("head")
def _head(params, x, dtype):
    """float32 predictions: the observation head, or logits over the
    vocabulary slice (a bias-free head; products in ``dtype``,
    accumulated in float32, the slice's weights never upcast), over the
    model's logit divisor where it has one."""
    out = _predict(params, x, dtype)
    return out / params["mup"].logits if "mup" in params else out


def _predict(params, x, dtype):
    if "head" not in params:  # tied: the embedding table, transposed
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          params["embed"]["table"].astype(dtype),
                          preferred_element_type=jnp.float32)
    head = params["head"]
    if "b" in head:
        return _dense_mq(head, x, jnp.float32)
    return jnp.einsum("...d,dv->...v", x.astype(dtype),
                      head["w"].astype(dtype),
                      preferred_element_type=jnp.float32)


def _diff_kind(blk):
    """The scope a differential block's attention runs under."""
    if "wk" not in blk:
        return "cross"
    return "full" if blk["diff"]["spec"].window is None else "window"


def _plain_qkv(blk, h, dtype, pos=None):
    """The bias-free projections of a plain attention block inside a
    model of mixed kinds: normed input ``h`` (..., d) -> ``(q (..., H,
    Dh), k, v (..., Hkv, Dh))``, ``Dh`` the weights' own.  ``q`` and ``k``
    go through the block's RMSNorm where it holds one (over each head
    where the scale is ``Dh`` wide, else over the whole projection), then
    through its rotation where its :class:`AttnSpec` has one: at ``pos``
    (B,), one position a row, or for a sequence ``(B, T, ...)`` at
    ``0 .. T - 1``."""
    q, k, v = (jnp.einsum("...d,dhk->...hk", h.astype(dtype),
                          blk[n].astype(dtype)) for n in ("wq", "wk", "wv"))

    def normed(name, t):
        if name not in blk:
            return t
        if blk[name]["scale"].shape[-1] == t.shape[-1]:
            return _ln_apply(blk[name], t)
        return _ln_apply(blk[name],
                         t.reshape(*t.shape[:-2], -1)).reshape(t.shape)

    q, k = normed("q_norm", q), normed("k_norm", k)
    spec = blk.get("attn")
    if spec is not None and spec.theta is not None:
        if pos is None:
            pos = jnp.arange(q.shape[-3])
        cos, sin = rope_table(pos, q.shape[-1], spec.theta, spec.yarn,
                              spec.attention_factor)
        # (T, Dh/2) or (B, Dh/2) tables over (B, T, H, Dh) or (B, H, Dh)
        q, k = apply_rope_rows(q, cos, sin), apply_rope_rows(k, cos, sin)
    return q, k, v


def _plain_out(blk, a, dtype):
    return jnp.einsum("...hk,hkd->...d", a.astype(dtype),
                      blk["wo"].astype(dtype))


def _attend_rows(q, kc, vc, pos, dtype, scale):
    """One query a row, ``q`` (B, H, Dh), over that row's cached keys and
    values, flat ``(B, C, Hkv * Dh)`` rows of a ring written at ``p % C``
    and masked by the absolute position a slot holds (as
    :func:`_attn_one`), at position ``pos`` (B,) and softmax ``scale`` ->
    (B, H, Dh) float32.
    Each K/V head is read where it lies, as the whole-lane columns of the
    rows (``diffattn.attend_one`` says why)."""
    b, c, _ = kc.shape
    h, dh = q.shape[1:]
    n_kv = kc.shape[-1] // dh
    qs = q.reshape(b, n_kv, h // n_kv, dh).astype(dtype)
    p_col = pos[:, None]
    keep = p_col - ((p_col - jnp.arange(c)[None]) % c) >= 0
    outs = []
    for i in range(n_kv):
        cols = slice(i * dh, (i + 1) * dh)
        s = jnp.einsum("bme,bce->bmc", qs[:, i], kc[..., cols].astype(dtype),
                       preferred_element_type=jnp.float32)
        w = jax.nn.softmax(jnp.where(keep[:, None], s * scale, -1e30),
                           axis=-1)
        outs.append(jnp.einsum(
            "bmc,bce->bme", w.astype(dtype), vc[..., cols].astype(dtype),
            preferred_element_type=jnp.float32))
    return jnp.stack(outs, axis=1).reshape(b, h, dh)


def _held_moe(blk, h, dtype, auxs, valid=None):
    """The held-share expert layer over ``h`` of any leading shape; its
    counts go to ``auxs``."""
    with jax.named_scope("moe"):
        y, counts = moe_apply_held(
            blk["moe"], h.reshape(-1, h.shape[-1]), dtype, valid=valid)
    auxs.append({"counts": counts})
    return y.reshape(h.shape)


def _ffn(blk, x, dtype, auxs, valid, moe_impl, moe_k, moe_capacity_factor,
         moe_dispatch):
    """``x`` plus the block's feed-forward branch, over ``(B, T, d)``
    positions or a decode step's ``(B, d)`` rows: the held share of a
    routed layer (its counts over the ``valid`` rows go to ``auxs``),
    the legacy expert entry as the ``moe_*`` arguments evaluate it (a
    routed evaluation's aux goes to ``auxs`` too), the gated MLP or the
    GELU MLP.  The one place the choice is made."""
    if "moe" in blk and "route" in blk["moe"]:
        return x + _post(blk, "ln2", _held_moe(
            blk, _pre(blk, "ln2", x), dtype, auxs, valid))
    with jax.named_scope("mlp"):
        h = _pre(blk, "ln2", x)
        if "moe" not in blk:
            if "gate" in blk["mlp"]:
                return x + _post(blk, "ln2", gated_mlp(blk["mlp"], h, dtype))
            h = gelu(_dense_mq(blk["mlp"]["fc"], h, dtype))
            return x + _post(blk, "ln2",
                             _dense_mq(blk["mlp"]["proj"], h, dtype))
        rows = h.ndim == 2
        if rows:
            h = h[:, None]  # the legacy layers take (B, T, d)
        if moe_impl == "topk":
            y, aux = moe_apply_topk(
                blk["moe"], h, dtype, k=moe_k,
                capacity_factor=moe_capacity_factor, dispatch=moe_dispatch)
            auxs.append(aux)
        elif moe_impl == "dense":
            y = _moe_apply(blk["moe"], h, dtype)
        else:
            raise ValueError(f"unknown moe_impl {moe_impl!r}")
        return x + _post(blk, "ln2", y[:, 0] if rows else y)


def _drop_free(params, moe_k, capacity_factor):
    """The capacity factor that leaves no assignment of the legacy
    routed layer without a slot (``>= e / k``).  The capacity bound
    exists to balance batched training dispatch and its value depends
    on the total token count, so capacity-bounded routing is not causal
    and can never match between incremental and full-sequence
    evaluation: decode steps, and a prefill they must agree with, route
    drop-free."""
    for blk in params["blocks"]:
        if "moe" in blk and "route" not in blk["moe"]:
            e = blk["moe"]["w1"].shape[0]
            return max(capacity_factor, e / min(moe_k, e))
    return capacity_factor


def token_model_specs(config, first=0):
    """The static entries of a token model's blocks, from the published
    keys: ``(blk["mla"]["spec"], blk["moe"]["route"])``."""
    c = config
    ys = c.get("rope_scaling")
    return (
        mla.MlaSpec(
            rope_dim=c["qk_rope_head_dim"], rope_base=float(c["rope_theta"]),
            yarn=None if not ys else (
                ys["factor"], ys["beta_fast"], ys["beta_slow"],
                ys["original_max_position_embeddings"], ys["mscale"],
                ys["mscale_all_dim"])),
        RouteSpec(top_k=c["num_experts_per_tok"],
                  scale=float(c["routed_scaling_factor"]), first=first),
    )


#: a published ``layer_types`` entry -> the kind it is here (a
#: ``granitemoehybrid`` model's ``mamba`` is Mamba-2, its ``attention``
#: full attention)
_LAYER_TYPES = {"linear_attention": "gdn", "full_attention": "full",
                "sliding_attention": "window", "mamba": "ssd",
                "attention": "full"}


def hybrid_layer_kinds(config):
    """The kind of every layer of a model of mixed layer kinds.  Where
    the configuration publishes ``layer_types`` they are its first
    ``num_hidden_layers`` entries (``"gdn"`` for ``linear_attention``,
    ``"full"`` for ``full_attention`` and ``attention``, ``"window"`` for
    ``sliding_attention``, ``"ssd"`` for ``mamba``).  Otherwise the rule of a
    decoder-hybrid-decoder model
    (arXiv:2507.06607), from ``num_hidden_layers`` and ``mb_per_layer``:
    in the first half every ``mb_per_layer``-th layer is ``"ssm"`` and
    the others ``"window"`` attention; the second half opens with one
    ``"ssm"`` (whose scan output is the memory of what follows) and one
    ``"full"`` attention (whose keys and values are the only full-length
    cache), then every ``mb_per_layer``-th layer is a ``"gmu"`` over that
    memory and the others ``"cross"`` attention over those keys and
    values."""
    if "layer_types" in config:
        types = config["layer_types"]
        if len(types) < config["num_hidden_layers"]:
            raise ValueError(f"{len(types)} layer_types for "
                             f"{config['num_hidden_layers']} layers")
        return [_LAYER_TYPES[t] for t in types[:config["num_hidden_layers"]]]
    n, every = config["num_hidden_layers"], config["mb_per_layer"]
    half = n // 2
    kinds = []
    for layer in range(n):
        if layer == half + 1:
            kinds.append("full")
        elif layer <= half:
            kinds.append("ssm" if layer % every == 0 or layer == half
                         else "window")
        else:
            kinds.append("gmu" if layer % every == 0 else "cross")
    return kinds


def _describe_hybrid(arrays, config):
    """A hybrid model's static entries: each norm's epsilon, and each
    differential block's ``lam_init`` and window."""
    eps = NormSpec(float(config["layer_norm_eps"]))
    kinds = hybrid_layer_kinds(config)
    if len(kinds) != len(arrays["blocks"]):
        raise ValueError(f"{len(arrays['blocks'])} blocks for {len(kinds)} "
                         "layers")
    arrays["ln_f"]["spec"] = eps
    for layer, (kind, blk) in enumerate(zip(kinds, arrays["blocks"])):
        blk["ln1"]["spec"] = blk["ln2"]["spec"] = eps
        holds = ("ssm" if "ssm" in blk else "gmu" if "gmu" in blk
                 else "cross" if "wk" not in blk else "self")
        if holds != ("self" if kind in ("window", "full") else kind):
            raise ValueError(f"layer {layer} is {kind!r} by the "
                             f"configuration and holds {sorted(blk)}")
        if "diff" in blk:
            blk["diff"]["spec"] = diffattn.DiffSpec(
                diffattn.lam_init_of(layer),
                config["sliding_window"] if kind == "window" else None)
    return arrays


def init_hybrid_model(key, config, dtype=jnp.float32):
    """A decoder-hybrid-decoder token model from the published keys
    ``hidden_size, num_attention_heads, num_key_value_heads,
    intermediate_size, num_hidden_layers, mb_per_layer, sliding_window,
    layer_norm_eps, vocab_size`` (tied embedding, LayerNorm, gated SiLU
    feed-forwards, no positional encoding) and the state-space sizes
    ``mamba_d_state, mamba_d_conv, mamba_expand, mamba_dt_rank``."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    d_inner = c["mamba_expand"] * d
    kinds = hybrid_layer_kinds(c)
    ke, *kb = jax.random.split(key, 1 + len(kinds))

    def norm():
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}

    blocks = []
    for kind, k in zip(kinds, kb):
        km, kf = jax.random.split(k)
        blk = {"ln1": norm(), "ln2": norm(),
               "mlp": gated_mlp_init(kf, d, c["intermediate_size"], dtype)}
        if kind == "ssm":
            blk["ssm"] = mamba.init(km, d, d_inner, c["mamba_d_state"],
                                    c["mamba_d_conv"], c["mamba_dt_rank"],
                                    dtype)
        elif kind == "gmu":
            blk["gmu"] = mamba.gmu_init(km, d, d_inner, dtype)
        else:
            blk.update(diffattn.init(
                km, d, heads, c["num_key_value_heads"], d // heads, None,
                cross=kind == "cross", dtype=dtype))
        blocks.append(blk)
    return _describe_hybrid({
        "embed": {"table": scaled_normal(ke, (c["vocab_size"], d), 1.0,
                                         dtype)},
        "blocks": blocks, "ln_f": norm()}, c)


def _attn_spec(config, layer_type, kind):
    """A plain attention layer's :class:`AttnSpec` from the published
    keys, or None where it has no window, no rotation and the default
    scale: ``sliding_window`` for a window layer, ``attention_multiplier``
    for the scale, and ``rope_parameters`` (one entry for every kind, or
    an entry by layer type) of ``rope_type`` ``default`` or ``yarn``; a
    ``rope_theta`` of null, or a ``position_embedding_type`` of ``nope``,
    is no rotation."""
    window = config["sliding_window"] if kind == "window" else None
    scale = config.get("attention_multiplier")
    scale = None if scale is None else float(scale)
    rope = config.get("rope_parameters") or {}
    rope = rope.get(layer_type, rope)
    if (rope.get("rope_theta") is None
            or config.get("position_embedding_type") == "nope"):
        if window is None and scale is None:
            return None
        return AttnSpec(window, scale=scale)
    yarn = factor = None
    rope_type = rope.get("rope_type", "default")
    if rope_type == "yarn":
        yarn = (float(rope["factor"]), rope["beta_fast"], rope["beta_slow"],
                rope["original_max_position_embeddings"])
        factor = rope.get("attention_factor")
        factor = float(yarn_mscale(yarn[0]) if factor is None else factor)
    elif rope_type != "default":
        raise ValueError(f"rope_type {rope_type!r} is not served")
    return AttnSpec(window, float(rope["rope_theta"]), yarn, factor, scale)


def _mup_spec(config):
    """A model's :class:`MupSpec` from the published keys
    ``embedding_multiplier``, ``residual_multiplier`` and
    ``logits_scaling``, or None where it publishes none of them."""
    keys = ("embedding_multiplier", "residual_multiplier", "logits_scaling")
    if not any(key in config for key in keys):
        return None
    return MupSpec(*(float(config.get(key, 1.0)) for key in keys))


def _describe_layer_types(arrays, config, first=0):
    """The static entries of a model whose configuration lists its
    ``layer_types`` (linear attention, Mamba-2, window and full
    attention): each norm's epsilon, each linear block's
    :class:`deltanet.GdnSpec`, each Mamba-2 block's
    :class:`mamba2.SsdSpec`, each plain attention block's
    :class:`AttnSpec` where its kind has a window, a rotation or a scale
    of its own, the :class:`MupSpec` where the model has one, and each
    routed layer's ``RouteSpec``: softmax over all ``num_experts``, the
    ``num_experts_per_tok`` renormalised where ``norm_topk_prob`` says
    so, the held experts from ``first``."""
    eps = float(config["rms_norm_eps"])
    kinds = hybrid_layer_kinds(config)
    if len(kinds) != len(arrays["blocks"]):
        raise ValueError(f"{len(arrays['blocks'])} blocks for {len(kinds)} "
                         "layers")
    arrays["ln_f"]["spec"] = NormSpec(eps)
    mup = _mup_spec(config)
    if mup:
        arrays["mup"] = mup
    for layer, (kind, layer_type, blk) in enumerate(zip(
            kinds, config["layer_types"], arrays["blocks"])):
        held = _mixer(blk)
        if (held[0] if held else None) != (kind if kind in _MIXERS
                                           else None):
            raise ValueError(f"layer {layer} is {kind!r} by the "
                             f"configuration and holds {sorted(blk)}")
        for name in ("ln1", "ln2", "post_ln1", "post_ln2", "q_norm",
                     "k_norm"):
            if name in blk:
                blk[name]["spec"] = NormSpec(eps)
        if mup:
            blk["mup"] = mup
        if kind == "gdn":
            blk["gdn"]["spec"] = deltanet.GdnSpec(
                bool(config["linear_allow_neg_eigval"]), eps)
        elif kind == "ssd":
            blk["ssd"]["spec"] = mamba2.SsdSpec(
                int(config["mamba_chunk_size"]), eps)
        elif spec := _attn_spec(config, layer_type, kind):
            blk["attn"] = spec
        if "moe" in blk:
            blk["moe"]["route"] = RouteSpec(
                top_k=config["num_experts_per_tok"], first=first,
                score="softmax", renorm=bool(config["norm_topk_prob"]))
    return arrays


def _init_recurrent_hybrid(key, config, mixer, norms, d_ff, table_fan_in,
                           qk_norm, dtype):
    """The blocks, embedding and head of a token model whose recurrent
    layers (``hybrid_layer_kinds``' kind ``mixer[0]``, built by
    ``mixer[1](key)``) stand beside plain grouped-query attention: per
    block the RMSNorms named ``norms`` and a gated SiLU feed-forward of
    ``d_ff``; the attention head-major, with q/k norms over the whole
    projection if ``qk_norm``; the table normal at ``table_fan_in ** -0.5``;
    an untied head unless the configuration ties it."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    kinds = hybrid_layer_kinds(c)
    ke, kh, *kb = jax.random.split(key, 2 + len(kinds))

    def norm(width):
        return {"scale": jnp.ones((width,), dtype)}

    blocks = []
    for kind, k in zip(kinds, kb):
        km, kf = jax.random.split(k)
        blk = {name: norm(d) for name in norms}
        blk["mlp"] = gated_mlp_init(kf, d, d_ff, dtype)
        if kind == mixer[0]:
            blk[kind] = mixer[1](km)
        else:
            h_kv, dh = c["num_key_value_heads"], d // heads
            kq, kk, kv, ko = jax.random.split(km, 4)
            blk.update(
                wq=scaled_normal(kq, (d, heads, dh), d, dtype),
                wk=scaled_normal(kk, (d, h_kv, dh), d, dtype),
                wv=scaled_normal(kv, (d, h_kv, dh), d, dtype),
                wo=scaled_normal(ko, (heads, dh, d), heads * dh, dtype))
            if qk_norm:
                blk.update(q_norm=norm(heads * dh), k_norm=norm(h_kv * dh))
        blocks.append(blk)
    model = {"embed": {"table": scaled_normal(
                 ke, (c["vocab_size"], d), table_fan_in, dtype)},
             "blocks": blocks, "ln_f": norm(d)}
    if not c.get("tie_word_embeddings"):
        model["head"] = {"w": scaled_normal(kh, (d, c["vocab_size"]), d,
                                            dtype)}
    return _describe_layer_types(model, c)


def init_linear_hybrid_model(key, config, dtype=jnp.float32):
    """A token model of gated delta-rule linear attention beside full
    attention, from the published keys ``hidden_size, intermediate_size,
    num_hidden_layers, num_attention_heads, num_key_value_heads,
    layer_types, linear_num_key_heads, linear_num_value_heads,
    linear_key_head_dim, linear_value_head_dim, linear_conv_kernel_dim,
    linear_allow_neg_eigval, rms_norm_eps, vocab_size,
    tie_word_embeddings``: RMSNorm after every sublayer and before the
    head, gated SiLU feed-forwards, a q/k norm over the whole projection
    in the full layers, no positional encoding, no biases."""
    c = config
    if c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError("linear key and value heads differ in number")

    def mixer(key):
        return deltanet.init(
            key, c["hidden_size"], c["linear_num_key_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], dtype=dtype)
    return _init_recurrent_hybrid(
        key, c, ("gdn", mixer), ("post_ln1", "post_ln2"),
        c["intermediate_size"], 1.0, True, dtype)


def init_ssd_hybrid_model(key, config, dtype=jnp.float32):
    """A token model of Mamba-2 layers beside full attention (a
    ``granitemoehybrid`` configuration without experts), from the
    published keys ``hidden_size, shared_intermediate_size,
    num_hidden_layers, num_attention_heads, num_key_value_heads,
    layer_types, mamba_n_heads, mamba_d_head, mamba_d_state, mamba_d_conv,
    mamba_expand, mamba_n_groups, mamba_chunk_size, rms_norm_eps,
    vocab_size, tie_word_embeddings`` and the muP multipliers: RMSNorm
    before every sublayer and the head, gated SiLU feed-forwards, no
    positional encoding, no biases but the convolutions'."""
    c = config
    d = c["hidden_size"]
    if c["mamba_n_groups"] != 1 or c.get("num_local_experts"):
        raise ValueError("one group of B and C and no experts are served")
    if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x "
                         "hidden_size")

    def mixer(key):
        return mamba2.init(key, d, c["mamba_n_heads"], c["mamba_d_head"],
                           c["mamba_d_state"], c["mamba_d_conv"], dtype)
    return _init_recurrent_hybrid(
        key, c, ("ssd", mixer), ("ln1", "ln2"),
        c["shared_intermediate_size"], d, False, dtype)


def describe_token_model(arrays, config, first=0):
    """Weights made elsewhere in this layout (arrays only) become a model:
    every block is given its static entries.  Returns ``arrays``.  A
    configuration with ``layer_types`` or ``mb_per_layer`` describes a
    model of mixed layer kinds (:func:`hybrid_layer_kinds`).  ``first``
    is the first routed expert held here."""
    if "layer_types" in config:
        return _describe_layer_types(arrays, config, first)
    if "mb_per_layer" in config:
        return _describe_hybrid(arrays, config)
    mla_spec, route = token_model_specs(config, first)
    for blk in arrays["blocks"]:
        blk["mla"]["spec"] = mla_spec
        if "moe" in blk:
            blk["moe"]["route"] = route
    return arrays


def init_token_model(key, config, held=None, dtype=jnp.float32):
    """A token model with latent attention and sigmoid-routed experts,
    from a configuration in the published (Hugging Face) keys:
    ``hidden_size, num_attention_heads, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, rope_theta, rope_scaling,
    num_hidden_layers, first_k_dense_replace, intermediate_size,
    moe_intermediate_size, num_experts, num_experts_per_tok,
    routed_scaling_factor, num_shared_experts, vocab_size``.

    ``held = (first, count)`` is the share of the routed experts this
    rank holds (all by default): the router keeps its published width.
    The pytree it returns is what :func:`_forward`, :func:`init_cache`
    and :func:`decode_step` dispatch on (module docstring); the static
    ``spec`` / ``route`` entries carry what shapes cannot."""
    c = config
    d = c["hidden_size"]
    first, count = held or (0, c["num_experts"])
    mla_spec, route = token_model_specs(c, first)
    ke, kh, *kb = jax.random.split(key, 2 + c["num_hidden_layers"])

    def norm(width):
        return {"scale": jnp.ones((width,), dtype)}

    blocks = []
    for i, k in enumerate(kb):
        ka, km = jax.random.split(k)
        blk = {"ln1": norm(d), "ln2": norm(d),
               "mla": mla.init(ka, d, c["num_attention_heads"],
                               c["kv_lora_rank"], c["qk_nope_head_dim"],
                               c["v_head_dim"], mla_spec, dtype)}
        if i < c["first_k_dense_replace"]:
            blk["mlp"] = gated_mlp_init(km, d, c["intermediate_size"], dtype)
        else:
            blk["moe"] = held_init(
                km, d, c["moe_intermediate_size"], c["num_experts"], count,
                route, shared=bool(c.get("num_shared_experts")), dtype=dtype)
        blocks.append(blk)
    return {
        "embed": {"table": scaled_normal(ke, (c["vocab_size"], d), 1.0,
                                         dtype)},
        "blocks": blocks,
        "ln_f": norm(d),
        "head": {"w": scaled_normal(kh, (d, c["vocab_size"]), d, dtype)},
    }


def init(
    key,
    obs_dim=8,
    d_model=64,
    n_heads=4,
    n_layers=2,
    d_ff=None,
    n_experts=0,
    max_len=1024,
    n_kv_heads=None,
    pos_encoding="learned",
):
    """Initialize SeqFormer params.

    ``n_experts=0`` gives a dense MLP; ``n_experts>0`` the MoE variant.
    ``n_kv_heads < n_heads`` is grouped-query attention: k/v project to
    fewer heads (smaller params + KV bandwidth).  Grouped shapes are
    handled by ``full_attention`` (broadcast) and the flash kernel
    (KV-head-mapped BlockSpecs, group-summed dK/dV) behind the
    ``attn_fn`` seam; the ring sequence-parallel schemes reject them
    (their ring-level VJPs rotate per-q-head accumulators) — use
    ulysses or repeat kv heads upstream there.

    ``pos_encoding='rope'`` replaces the learned position table with
    rotary embeddings applied to q/k: positions become RELATIVE, so
    sequence length — training or :func:`rollout` horizon — is no
    longer bounded by ``max_len`` (which is then ignored), and the
    rotation happens before the ``attn_fn`` seam so every attention
    scheme (flash, windowed, GQA, ring/ulysses sequence parallelism)
    composes unchanged.  Practical horizon ~1e5-1e6 positions — f32
    angle precision, see :func:`blendjax.models.layers.rope_table`.
    """
    d_ff = d_ff or 4 * d_model
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} not divisible by n_heads {n_heads}")
    n_kv_heads = n_kv_heads or n_heads
    if n_heads % n_kv_heads:
        raise ValueError(
            f"n_heads {n_heads} not divisible by n_kv_heads {n_kv_heads}"
        )
    dh = d_model // n_heads
    if pos_encoding == "rope" and dh % 2:
        raise ValueError(f"rope needs an even head dim, got {dh}")
    if pos_encoding not in ("learned", "rope"):
        raise ValueError(f"unknown pos_encoding {pos_encoding!r}")
    keys = jax.random.split(key, 3 + n_layers)
    params = {
        "embed": dense_init(keys[0], obs_dim, d_model),
        "blocks": [],
        "ln_f": _ln_init(d_model),
        "head": dense_init(keys[2], d_model, obs_dim),
    }
    if pos_encoding == "learned":
        # absence of the table IS the rope marker: the checkpoint stays
        # a plain array pytree and remains self-describing
        params["pos"] = jax.random.normal(keys[1], (max_len, d_model)) * 0.02
    scale = jnp.sqrt(1.0 / d_model)
    for i in range(n_layers):
        ka, km = jax.random.split(keys[3 + i])
        kq, kk, kv, ko = jax.random.split(ka, 4)
        # Head-major projection layout (d, H, Dh)/(H, Dh, d): the head axis
        # is a real array axis, so tensor parallelism shards it directly
        # (seqformer_rules) and n_heads is recoverable from the shapes.
        block = {
            "ln1": _ln_init(d_model),
            "wq": {"w": jax.random.normal(kq, (d_model, n_heads, dh)) * scale,
                   "b": jnp.zeros((n_heads, dh))},
            "wk": {"w": jax.random.normal(kk, (d_model, n_kv_heads, dh))
                   * scale,
                   "b": jnp.zeros((n_kv_heads, dh))},
            "wv": {"w": jax.random.normal(kv, (d_model, n_kv_heads, dh))
                   * scale,
                   "b": jnp.zeros((n_kv_heads, dh))},
            "wo": {"w": jax.random.normal(ko, (n_heads, dh, d_model)) * scale,
                   "b": jnp.zeros((d_model,))},
            "ln2": _ln_init(d_model),
        }
        if n_experts > 0:
            block["moe"] = _moe_init(km, n_experts, d_model, d_ff)
        else:
            k1, k2 = jax.random.split(km)
            block["mlp"] = {
                "fc": dense_init(k1, d_model, d_ff),
                "proj": dense_init(k2, d_ff, d_model),
            }
        params["blocks"].append(block)
    return params


def _forward(params, obs, attn_fn=None, compute_dtype=jnp.bfloat16,
             moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
             moe_dispatch="sort", kv_sink=None, last_only=False,
             state_in=None, diff_attn_fn=None):
    """Shared forward: returns (prediction, list of per-layer MoE aux).

    ``kv_sink`` (a list) collects, block by block, what the block gives
    the cache under the cache's own names (``k`` and ``v``, a latent
    block's ``kv`` rows, a recurrent block's state after the last position
    under :data:`_MIXERS`' names, the tails flat; nothing for a block
    that keeps nothing) —
    :func:`rollout`'s vectorized prefill fills its caches from one
    teacher-forced pass instead of t0 serial decode steps.
    ``state_in`` (``{name: [...]}`` by block, under the same names) is
    the recurrent state the recurrent blocks start from, zeros by
    default.  ``diff_attn_fn(q, k, v, scale, window)`` is the causal
    attention under the attention blocks of a model of mixed kinds
    (plain by default).
    ``last_only`` answers for the
    last position alone (a prefill over a vocabulary wants no other
    logits).  The ``moe_*`` arguments choose the evaluation of the
    legacy expert entry only; a held-share layer carries its own."""
    if attn_fn is None:
        def attn_fn(q, k, v):
            return full_attention(q, k, v, causal=True)

    if diff_attn_fn is None:
        def diff_attn_fn(q, k, v, scale, window):
            return full_attention(q, k, v, causal=True, scale=scale,
                                  window=window)

    t = obs.shape[1]
    auxs = []
    sink = [] if kv_sink is None else kv_sink
    hybrid = _hybrid(params)
    use_rope = "pos" not in params and not _latent(params) and not hybrid
    x = _embed(params, obs, compute_dtype)
    if use_rope:
        dh = _wq_head_dim(params)
        cos, sin = rope_table(jnp.arange(t), dh)
    elif "pos" in params:
        x = x + params["pos"][:t].astype(compute_dtype)[None]
    memory = shared_kv = None
    for i, blk in enumerate(params["blocks"]):
        mixer = _mixer(blk)
        if "mla" in blk:
            with jax.named_scope("mla"):
                h = _pre(blk, "ln1", x)
                q_nope, q_pe, rows = mla.project(
                    blk["mla"], h, *mla.rope(blk["mla"], jnp.arange(t)),
                    compute_dtype)
                sink.append({"kv": rows})
                x = x + _post(blk, "ln1", mla.attend_expanded(
                    blk["mla"], q_nope, q_pe, rows, compute_dtype))
        elif mixer:
            kind, module, names = mixer
            with jax.named_scope(kind):
                shapes = module.state_shapes(blk[kind])
                if state_in is None:
                    state = [jnp.zeros((x.shape[0], *shape),
                                       compute_dtype if j else jnp.float32)
                             for j, shape in enumerate(shapes)]
                else:
                    state = [state_in[name][i].reshape(-1, *shape)
                             for name, shape in zip(names, shapes)]
                out, *state = module.mix_sequence(
                    blk[kind], _pre(blk, "ln1", x), *state, compute_dtype)
                if kind == "ssm":  # its scan output, for the memory units
                    memory, *state = state
                # the float32 state as its module shapes it, the tails
                # flat, as the cache keeps them
                sink.append({name: part if j == 0
                             else part.reshape(part.shape[0], -1)
                             for j, (name, part) in enumerate(
                                 zip(names, state))})
                x = x + _post(blk, "ln1", out)
        elif "gmu" in blk:
            with jax.named_scope("gmu"):
                sink.append({})
                x = x + _post(blk, "ln1", mamba.gmu(
                    blk["gmu"], _pre(blk, "ln1", x), memory, compute_dtype))
        elif "diff" in blk:
            with jax.named_scope("attn"):
                h = _pre(blk, "ln1", x)
                q = diffattn.project_q(blk, h, compute_dtype)
                kept = {}
                if "wk" in blk:
                    shared_kv = diffattn.project_kv(blk, h, compute_dtype)
                    kept = {name: kv.reshape(*kv.shape[:2], -1)
                            for name, kv in zip(("k", "v"), shared_kv)}
                sink.append(kept)
                with jax.named_scope(_diff_kind(blk)):
                    x = x + _post(blk, "ln1", diffattn.attend(
                        blk, q, *shared_kv, compute_dtype, diff_attn_fn))
        elif hybrid:
            with jax.named_scope("attn"):
                q, k, v = _plain_qkv(blk, _pre(blk, "ln1", x), compute_dtype)
                sink.append({"k": k.reshape(*k.shape[:2], -1),
                             "v": v.reshape(*v.shape[:2], -1)})
                window = _window_of(blk)
                with jax.named_scope("window" if window else "full"):
                    a = diff_attn_fn(q, k, v, _scale_of(blk, q.shape[-1]),
                                     window)
                x = x + _post(blk, "ln1", _plain_out(blk, a, compute_dtype))
        else:
            with jax.named_scope("attn"):
                h = _pre(blk, "ln1", x)
                q, k, v = (
                    _proj_mq(blk[n], h, "btd,dhk->bthk", compute_dtype)
                    for n in ("wq", "wk", "wv")
                )
                if use_rope:
                    # rotate BEFORE the kv sink and the attn seam: caches
                    # store rotated keys, and every attention scheme sees
                    # pre-rotated q/k (rotation by absolute position makes
                    # scores relative)
                    q = apply_rope(q, cos, sin)
                    k = apply_rope(k, cos, sin)
                sink.append({"k": k, "v": v})
                a = attn_fn(q, k, v)
                x = x + _post(blk, "ln1", _proj_mq(
                    blk["wo"], a, "bthk,hkd->btd", compute_dtype))
        x = _ffn(blk, x, compute_dtype, auxs, None, moe_impl, moe_k,
                 moe_capacity_factor, moe_dispatch)
    if last_only:
        x = x[:, -1:]
    x = _ln_apply(params["ln_f"], x)
    return _head(params, x, compute_dtype), auxs


def apply(params, obs, attn_fn=None, compute_dtype=jnp.bfloat16,
          moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
          moe_dispatch="sort"):
    """Forward pass: (B, T, obs_dim) -> (B, T, obs_dim) next-obs prediction.

    ``attn_fn(q, k, v) -> out`` with (B, T, H, Dh) tensors; defaults to
    single-device causal :func:`full_attention`.  Pass a
    ``make_ring_attention(mesh, causal=True, ...)`` closure to shard the
    sequence axis.  ``moe_impl``: 'dense' evaluates every expert
    (gate-weighted mixture), 'topk' routes each token to ``moe_k`` experts
    under a capacity bound (:mod:`blendjax.models.moe`).
    """
    out, _ = _forward(
        params, obs, attn_fn, compute_dtype, moe_impl, moe_k,
        moe_capacity_factor, moe_dispatch,
    )
    return out


def loss_fn(params, batch, attn_fn=None, compute_dtype=jnp.bfloat16,
            moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
            moe_aux_weight=0.0, moe_dispatch="sort"):
    """MSE next-observation loss (+ optional MoE load-balance aux term).

    ``batch = {'obs': (B,T,D), 'target': (B,T,D)}`` — the target is the
    obs sequence shifted host-side (so the device-side loss needs no
    cross-shard shift when T is sequence-sharded).  With
    ``moe_impl='topk'`` and ``moe_aux_weight > 0`` the Switch-style load
    balance loss (mean over layers) is added, pushing the router toward
    uniform expert load.
    """
    pred, auxs = _forward(
        params, batch["obs"], attn_fn, compute_dtype, moe_impl, moe_k,
        moe_capacity_factor, moe_dispatch,
    )
    err = pred - batch["target"].astype(jnp.float32)
    loss = jnp.mean(err * err)
    if auxs and moe_aux_weight:
        loss = loss + moe_aux_weight * sum(
            a["aux_loss"] for a in auxs
        ) / len(auxs)
    return loss


def moe_stats(params, batch, attn_fn=None, compute_dtype=jnp.bfloat16,
              moe_k=2, moe_capacity_factor=1.25, moe_dispatch="sort"):
    """Measured routing statistics for the topk MoE path (jit this).

    Returns ``{'dispatch_fraction': scalar, 'aux_loss': scalar}`` — means
    over layers of the fraction of (token, choice) assignments that won a
    capacity slot, and of the Switch load-balance loss.  The benchmark
    reports THIS measured fraction, not the analytic ``k/e`` bound
    (VERDICT r3 weak #3: a constant dressed as a measurement).
    """
    _, auxs = _forward(
        params, batch["obs"], attn_fn, compute_dtype, "topk", moe_k,
        moe_capacity_factor, moe_dispatch,
    )
    if not auxs:
        raise ValueError(
            "moe_stats needs params built with n_experts > 0 — these "
            "params contain no MoE blocks, so there is no routing to "
            "measure"
        )
    n = len(auxs)
    return {
        "dispatch_fraction": sum(a["dispatch_fraction"] for a in auxs) / n,
        "aux_loss": sum(a["aux_loss"] for a in auxs) / n,
    }


def make_episode_batch(obs_seq):
    """Host-side helper: episode array (B, T+1, D) -> {'obs', 'target'}."""
    return {"obs": obs_seq[:, :-1], "target": obs_seq[:, 1:]}


def episode_loss_fn(params, batch, **kwargs):
    """:func:`loss_fn` over a wire-efficient batch ``{'episode':
    (B, T+1, D)}``: the obs/target views are sliced ON DEVICE (the same
    :func:`make_episode_batch` split, applied to the traced array).

    :func:`make_episode_batch` materializes two host arrays whose
    contents overlap in all but one timestep, so a feed that transfers
    its output moves ~2x the episode's bytes host->device.  Streaming
    the raw episode and slicing device-side halves the wire traffic;
    at equal input dtype the loss is identical (parity-tested).  A feed
    may additionally downcast the episode on the wire (e.g. float16 in
    the benchmark suite) — that is a disclosed input-precision choice,
    not loss-free: the float32 target comparison then sees quantized
    targets.

    Use with replicated or batch-sharded feeds.  For SEQUENCE-sharded
    training keep the host-side :func:`make_episode_batch` split: the
    device-side shift would need a cross-shard neighbor exchange there
    (see :func:`loss_fn`'s note on the sharded target).
    """
    with jax.named_scope("loss"):
        return loss_fn(
            params, make_episode_batch(batch["episode"]), **kwargs)


def train_flops(batch_size, seq_len, obs_dim, d_model, n_heads, n_layers,
                d_ff=None, n_experts=0, moe_impl="dense", moe_k=2,
                moe_capacity_factor=1.25):
    """Closed-form FLOPs of one training step (matmul terms only).

    Forward, per token: qkv+out projections ``8*d^2``, attention scores +
    apply ``4*T*d`` (full T^2 — :func:`full_attention` computes the whole
    matrix and masks, so the causal half is NOT discounted; a kernel that
    skips masked blocks, e.g. the Pallas flash path, will show mfu ~2x
    against this count and the benchmark reports both counts so that is
    visible), MLP ``4*d*d_ff``.  MoE: 'dense' evaluates every expert
    (``n_experts * 4*d*d_ff`` + gate); 'topk' fills ``e*capacity =
    ~k*cf*n`` arena rows, so expert compute is ``k*cf`` times the single
    -MLP term regardless of routing collapse (static shapes).  Training
    = 3x forward; embed/head/layernorm/optimizer terms included where
    matmul-shaped, elementwise omitted.  Cross-checked against XLA's
    ``cost_analysis()`` by the benchmark suite (VERDICT r3 next #2).
    """
    B, T, d = batch_size, seq_len, d_model
    d_ff = d_ff or 4 * d
    tok = B * T
    fwd = 2.0 * tok * obs_dim * d  # embed
    per_layer = 8.0 * d * d + 4.0 * T * d  # qkvo + scores/apply per token
    if n_experts > 0:
        gate = 2.0 * d * n_experts
        if moe_impl == "topk":
            # static arena: e * ceil(k*n/e * cf) rows through the expert MLP
            import math

            cap = max(1, math.ceil(moe_k * tok / n_experts
                                   * moe_capacity_factor))
            expert_rows = n_experts * cap
            mlp = gate + 4.0 * d * d_ff * (expert_rows / tok)
        else:
            mlp = gate + n_experts * 4.0 * d * d_ff
    else:
        mlp = 4.0 * d * d_ff
    fwd += tok * n_layers * (per_layer + mlp)
    fwd += 2.0 * tok * d * obs_dim  # head
    return 3.0 * fwd


# -- autoregressive rollout (KV cache) --------------------------------------


def init_cache(params, batch_size, dtype=jnp.bfloat16, length=None,
               per_row=False):
    """Per-layer KV caches: ``{'k': [(B, L, Hkv, Dh)], 'v': [...],
    'pos': 0}``, or for a latent-attention model ``{'kv': [(B, L,
    kv_rank + rope)], 'pos': 0}``, one row a position
    (:mod:`blendjax.models.mla`).  A model of mixed layer kinds holds
    **three kinds of state side by side**, each list indexed by block
    with ``None`` where the block keeps nothing of that kind: a ring of
    ``window`` positions of keys and of values ``(B, window, Hkv * Dh)``
    per window layer, one full-length ``(B, L, Hkv * Dh)`` pair for the
    full layer (which the cross layers read), and per recurrent layer
    its module's state under :data:`_MIXERS`' names: a state-space layer's
    ``ssm_h`` ``(B, d_state, d_inner)`` float32 and ``ssm_tail`` ``(B,
    (d_conv - 1) * d_inner)``; a linear-attention layer's ``gdn_s`` ``(B,
    pieces, H / pieces, dv, dk)`` float32 (the heads cut in the fewest
    pieces that :func:`_pool_rows` gathers without the compiler slicing
    the whole pool) and three flat tails.  A plain attention layer of
    such a model keeps a ring of its window (:class:`AttnSpec`) or a
    full-length pair; a model of such layers alone keeps nothing else.
    Every leaf is flat behind its
    row or position, which is the layout the TPU compiler keeps as it is handed
    it (compiled for a described v5e; ``tests/test_tpu_compile.py``):
    a minor pair of axes like ``(Hkv / 2, 2 Dh)`` or ``(d_conv - 1,
    d_inner)``, whose second-minor axis is no whole tile, was re-laid
    on the way in and out, and pair-major ``(B, Hkv / 2, L, 2 Dh)`` keys
    were re-laid positions-major for the one-position write: either way
    the whole pool copied twice a layer.
    ``length`` defaults to the model's ``max_len`` (the
    ``pos`` table); pass the actual decode horizon to size the cache —
    and every step's attention — to the sequence you will run.  Rope
    models have no table and no inherent bound: ``length`` is required.

    ``per_row=True`` makes ``pos`` a ``(B,)`` int32 vector instead of
    the batch-uniform scalar: every cache row then decodes at its OWN
    position (:func:`decode_step` dispatches on ``pos``'s rank), which
    is what a serving tier needs to run ONE batched decode over live
    episodes at heterogeneous timesteps (``blendjax/serve``).
    :func:`prefill` admits a whole prefix into such rows and
    :func:`rewind_rows` resets them; with :func:`decode_step` they are
    everything that reads or writes the layout.
    """
    if length is None:
        if "pos" not in params:
            raise ValueError(
                "rope models have no max_len; pass the decode horizon "
                "as length="
            )
        length = params["pos"].shape[0]
    elif "pos" in params and length > params["pos"].shape[0]:
        # decode_step indexes the pos table with a traced position;
        # lax.dynamic_index_in_dim CLAMPS out-of-bounds, so steps past
        # max_len would silently reuse the last embedding — reject the
        # intent here, statically
        raise ValueError(
            f"cache length {length} exceeds the learned position "
            f"table ({params['pos'].shape[0]}); use pos_encoding='rope' "
            "for longer horizons"
        )
    pos0 = (
        jnp.zeros((batch_size,), jnp.int32)
        if per_row else jnp.asarray(0, jnp.int32)
    )
    if _hybrid(params):
        held = ("k", "v") + tuple(
            name for kind, (_, names) in _MIXERS.items()
            if any(kind in blk for blk in params["blocks"]) for name in names)
        caches = {"pos": pos0, **{name: [] for name in held}}
        for blk in params["blocks"]:
            own = dict.fromkeys(held)
            mixer = _mixer(blk)
            if mixer:
                kind, module, names = mixer
                for j, (name, shape) in enumerate(zip(
                        names, module.state_shapes(blk[kind]))):
                    own[name] = jnp.zeros(
                        (batch_size, *_state_leaf(j, shape)),
                        dtype if j else jnp.float32)
            elif "wk" in blk:
                _, h_kv, dh = blk["wk"].shape
                ring = min(_window_of(blk) or length, length)
                for name in ("k", "v"):
                    own[name] = jnp.zeros((batch_size, ring, h_kv * dh),
                                          dtype)
            for name, leaf in own.items():
                caches[name].append(leaf)
        return caches
    if _latent(params):
        # one latent row a position and layer: [RMSNorm(c) | rot(k_pe)]
        return {"pos": pos0, "kv": [
            jnp.zeros((batch_size, length, mla.row_width(blk["mla"])), dtype)
            for blk in params["blocks"]]}
    caches = {"k": [], "v": [], "pos": pos0}
    for blk in params["blocks"]:
        wk = blk["wk"]
        _, h_kv, dh = (wk["w"] if "w" in wk else wk["w_q"]).shape
        shape = (batch_size, length, h_kv, dh)
        caches["k"].append(jnp.zeros(shape, dtype))
        caches["v"].append(jnp.zeros(shape, dtype))
    return caches


def _attn_one(q, kc, vc, pos, scale, window=None):
    """Single-query attention over a (possibly ring-buffer) cache: q
    (B, H, Dh), kc/vc (B, C, Hkv, Dh).  The cache is written at
    ``slot = p % C``, so slot ``s`` currently holds absolute position
    ``pos - ((pos - s) mod C)`` — the latest position congruent to
    ``s`` that has been written.  Masking on that absolute position
    unifies the no-wrap case (C >= sequence: it reduces to ``s <= pos``)
    with the O(window)-memory ring (C >= window: overwritten slots fall
    outside the window by construction).  GQA broadcasts the cached
    heads.

    ``pos`` is either the batch-uniform scalar (training rollouts) or a
    ``(B,)`` vector — one position per row, giving a (B, C) mask so one
    batched decode serves episodes at heterogeneous timesteps (the
    serving tier's path).  The scalar branch is the exact pre-serving
    code: rollout numerics are untouched."""
    b, c, h_kv, dh = kc.shape
    h = q.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        slot_pos = pos - ((pos - jnp.arange(c)) % c)
        keep = slot_pos >= 0  # never-written slots: negative positions
        if window is not None:
            keep = jnp.logical_and(keep, slot_pos > pos - window)
        keep_g = keep[None, None, None]   # over (B, Hkv, G, C)
        keep_h = keep[None, None]         # over (B, H, C)
    else:
        p_col = pos[:, None]              # (B, 1)
        slot_pos = p_col - ((p_col - jnp.arange(c)[None]) % c)
        keep = slot_pos >= 0              # (B, C)
        if window is not None:
            keep = jnp.logical_and(keep, slot_pos > p_col - window)
        keep_g = keep[:, None, None, :]
        keep_h = keep[:, None, :]
    if h_kv != h:
        # grouped einsum straight against the un-repeated cache —
        # materializing a repeated copy per decode step would pay
        # exactly the KV bandwidth GQA exists to avoid
        g = h // h_kv
        qg = q.reshape(b, h_kv, g, dh).astype(jnp.float32)
        s = jnp.einsum("bkgd,blkd->bkgl", qg,
                       kc.astype(jnp.float32)) * scale
        s = jnp.where(keep_g, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgl,blkd->bkgd", p, vc.astype(jnp.float32))
        return out.reshape(b, h, dh)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * scale
    s = jnp.where(keep_h, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p, vc.astype(jnp.float32))


#: The largest slice, in elements, that one row of a gather may take
#: before the TPU compiler (jax 0.9.0, libtpu 0.0.34) first cuts the
#: WHOLE operand into slices that fit and materialises them
#: (``mini-gather-slice`` in the compiled module): a gather of 16 rows
#: of 1024 x 8 x 128 then copies all 65 rows of the operand.
_GATHER_SLICE_ELEMS = 1 << 18


def _gather_pieces(c, per_pos):
    """The fewest equal pieces of ``c`` positions of ``per_pos`` elements
    each that stay under :data:`_GATHER_SLICE_ELEMS` a piece."""
    return next((n for n in range(1, c + 1)
                 if c % n == 0 and c // n * per_pos <= _GATHER_SLICE_ELEMS), c)


def _pool_rows(pool, slots):
    """``pool[slots]`` for one ``(S, C, Hkv, Dh)`` cache tensor (or a
    latent ``(S, C, W)`` one), gathered as pieces of ``C / n`` positions
    from the free ``(S * n, C / n, ...)`` view with the smallest ``n`` whose
    pieces stay under :data:`_GATHER_SLICE_ELEMS`, so that only the
    stepped rows move.  The same values either way."""
    s, c, *rest = pool.shape
    n = _gather_pieces(c, math.prod(rest))
    if n == 1:
        return pool[slots]
    at = (slots[:, None] * n + jnp.arange(n, dtype=slots.dtype)).reshape(-1)
    return pool.reshape(s * n, c // n, *rest)[at].reshape(
        slots.shape[0], c, *rest)


def _state_leaf(j, shape):
    """The shape the ``j``-th part of a recurrent state takes behind its
    row in the pool: a tail (``j > 0``) flat; the float32 state as its
    module shapes it, its leading axis cut in the fewest pieces that keep
    a gathered slice under :data:`_GATHER_SLICE_ELEMS` (:func:`_pool_rows`
    reads ``(S, pieces, ...)`` by pieces)."""
    if j:
        return (math.prod(shape),)
    n = _gather_pieces(shape[0], math.prod(shape[1:]))
    return shape if n == 1 else (n, shape[0] // n, *shape[1:])


def state_row_bytes(cache):
    """Bytes of recurrent state (tails included) behind one row of
    ``cache``: what a decode step reads, and writes again, of each row it
    steps."""
    return sum(math.prod(leaf.shape[1:]) * leaf.dtype.itemsize
               for name in _RECURRENT for leaf in cache.get(name, ())
               if leaf is not None)


def decode_step(params, cache, obs_t, compute_dtype=jnp.bfloat16,
                moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
                moe_dispatch="sort", window=None, slots=None):
    """One incremental step: consume obs_t (B, obs_dim) at the cache's
    current position, return (next-obs prediction (B, obs_dim) float32,
    updated cache).  Mirrors :func:`_forward`'s block math exactly at a
    single position — parity with the teacher-forced forward is tested.

    The cache is a RING buffer: writes land at ``pos % C`` and masking
    is by each slot's absolute position (see :func:`_attn_one`), so a
    cache of ``C >= window`` slots supports an unbounded decode horizon
    at O(window) memory.  A cache shorter than the sequence with NO
    window effectively attends to the last ``C`` positions only —
    size the cache to the horizon (what :func:`rollout` does) unless
    you want exactly that.

    ``cache['pos']`` may be a ``(B,)`` vector (``init_cache(...,
    per_row=True)``): each row then embeds, rotates, writes its ring
    slot and masks at its OWN position, so one batched call decodes
    episodes at heterogeneous timesteps — the policy-serving tier's
    continuous-batching kernel (parity with per-episode scalar decode
    is locked by ``tests/test_serve.py``).  The scalar path is
    byte-for-byte the pre-serving code.

    ``slots`` (a ``(B,)`` int vector; per-row caches only) says where
    the batch's rows live in a cache LARGER than the batch — the
    serving tier's slot pool.  Row ``j`` then embeds at
    ``cache['pos'][slots[j]]``, writes ONE position of pool row
    ``slots[j]`` per layer and attends over that row, read back from
    the written pool (:func:`_pool_rows`); every other row of the
    returned cache is the input's, so a caller that donates the cache
    has it updated in place.  ``slots=None`` is the case ``slots =
    arange(B)`` (the cache IS the batch and nothing is read back), so
    there is one per-row path.  Duplicate slots (a padded bucket's pad
    row) all write the same row; which one lands is unspecified.
    """
    pred, new_cache, _ = _decode(
        params, cache, obs_t, compute_dtype, moe_impl, moe_k,
        moe_capacity_factor, moe_dispatch, window, slots)
    return pred, new_cache


def _decode(params, cache, obs_t, compute_dtype=jnp.bfloat16,
            moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
            moe_dispatch="sort", window=None, slots=None, valid=None):
    """:func:`decode_step`, returning the model's counts too
    (``(prediction, cache, auxs)``: each held-share layer's under
    ``counts``, a model of mixed kinds' :meth:`_HybridStep.counts` under
    ``live``); ``valid`` (B,) marks the rows those counts are over (a
    padded bucket's pad rows are computed like any other and counted by
    nobody)."""
    from jax import lax

    pool_pos = cache["pos"]
    per_row = jnp.ndim(pool_pos) == 1
    if slots is not None and not per_row:
        raise ValueError(
            "slots= indexes a per-row cache (init_cache(per_row=True))"
        )
    # the scopes `gather` (the read of the stepped rows) and `scatter`
    # (the one-position write) are what a trace of the serving step
    # shows (PERF.md section 3)
    pos = pool_pos
    if slots is not None:
        with jax.named_scope("gather"):
            pos = pool_pos[slots]
    latent, hybrid = _latent(params), _hybrid(params)
    if latent and window is not None:
        raise ValueError("latent attention has no windowed path")
    _windows_are_described(hybrid, window)
    use_rope = "pos" not in params and not latent and not hybrid
    moe_capacity_factor = _drop_free(params, moe_k, moe_capacity_factor)
    auxs = []
    x = _embed(params, obs_t, compute_dtype)
    if use_rope:
        cos, sin = rope_table(pos if per_row else pos[None],
                              _wq_head_dim(params))
    elif latent or hybrid:
        pass  # a latent block rotates by its own table; no encoding at all
    elif per_row:
        # per-row table lookup; clip mirrors dynamic_index_in_dim's
        # out-of-bounds clamp on the scalar path (init_cache rejects
        # horizons past the table statically)
        x = x + jnp.take(params["pos"], pos, axis=0,
                         mode="clip").astype(compute_dtype)
    else:
        x = x + lax.dynamic_index_in_dim(
            params["pos"], pos, keepdims=False
        ).astype(compute_dtype)[None]
    if slots is None:
        rows = jnp.arange(obs_t.shape[0]) if per_row else None
        new_pos = pos + 1
    else:
        rows = slots
        with jax.named_scope("scatter"):
            new_pos = pool_pos.at[slots].set(pos + 1)
    new_cache = {"pos": new_pos}
    for name in cache:
        if name != "pos":
            new_cache[name] = []
    if hybrid:
        step = _HybridStep(cache, new_cache, obs_t.shape[0], pos, rows,
                           slots, compute_dtype)
        auxs.append(step.counts(params, valid))
    for i, blk in enumerate(params["blocks"]):
        if "mla" in blk:
            with jax.named_scope("mla"):
                x = x + _post(blk, "ln1", _mla_step(
                    blk, cache["kv"][i], new_cache["kv"], x, pos, rows,
                    slots, compute_dtype))
        elif hybrid:
            x = x + _post(blk, "ln1",
                          step.mix(i, blk, _pre(blk, "ln1", x)))
        else:
            with jax.named_scope("attn"):
                h = _pre(blk, "ln1", x)
                q = _proj_mq(blk["wq"], h, "bd,dhk->bhk", compute_dtype)
                k_new = _proj_mq(blk["wk"], h, "bd,dhk->bhk", compute_dtype)
                v_new = _proj_mq(blk["wv"], h, "bd,dhk->bhk", compute_dtype)
                if use_rope:
                    if per_row:
                        q = apply_rope_rows(q, cos, sin)
                        k_new = apply_rope_rows(k_new, cos, sin)
                    else:
                        q = apply_rope(q, cos, sin)
                        k_new = apply_rope(k_new, cos, sin)
                # ring buffer (see _attn_one)
                slot = pos % cache["k"][i].shape[1]
                if per_row:
                    # scatter each row's k/v at ITS ring slot: one position
                    # of the cache changes per row, the rest is the input's
                    with jax.named_scope("scatter"):
                        kc = cache["k"][i].at[rows, slot].set(
                            k_new.astype(cache["k"][i].dtype)
                        )
                        vc = cache["v"][i].at[rows, slot].set(
                            v_new.astype(cache["v"][i].dtype)
                        )
                else:
                    kc = lax.dynamic_update_slice_in_dim(
                        cache["k"][i], k_new[:, None].astype(cache["k"][i].dtype),
                        slot, axis=1,
                    )
                    vc = lax.dynamic_update_slice_in_dim(
                        cache["v"][i], v_new[:, None].astype(cache["v"][i].dtype),
                        slot, axis=1,
                    )
                new_cache["k"].append(kc)
                new_cache["v"].append(vc)
                if slots is not None:
                    # write first, then read: the stepped rows come out of
                    # the WRITTEN pool, so nothing orders a copy of it
                    with jax.named_scope("gather"):
                        kc, vc = _pool_rows(kc, slots), _pool_rows(vc, slots)
                dh = q.shape[-1]
                a = _attn_one(q, kc, vc, pos, 1.0 / jnp.sqrt(dh),
                              window=window).astype(compute_dtype)
                x = x + _post(blk, "ln1", _proj_mq(
                    blk["wo"], a, "bhk,hkd->bd", compute_dtype))
        x = _ffn(blk, x, compute_dtype, auxs, valid, moe_impl, moe_k,
                 moe_capacity_factor, moe_dispatch)
    x = _ln_apply(params["ln_f"], x)
    return _head(params, x, compute_dtype), new_cache, auxs


class _HybridStep:
    """One decode step's walk over a model of mixed layer kinds: what the
    layers hand each other (the last state-space layer's scan output,
    the stepped rows of the last written keys and values) and where each
    block's state goes.  ``pos`` is a scalar or one position a row."""

    def __init__(self, cache, new_cache, b, pos, rows, slots, dtype):
        self.cache, self.new, self.dtype = cache, new_cache, dtype
        self.pos = jnp.broadcast_to(pos, (b,))
        self.rows = jnp.arange(b) if rows is None else rows
        self.slots = slots
        self.memory = self.kv_rows = None

    def counts(self, params, valid):
        """``HYBRID_EVENTS``' device half over the ``valid`` rows (all by
        default): the live positions of the rows stepped (the one being
        written included), the rows, and the positions live in one
        window ring (the first window layer's, differential or plain)."""
        live = self.pos + 1
        valid = jnp.ones_like(live, bool) if valid is None else valid
        window = next((_window_of(blk) for blk in params["blocks"]
                       if "wk" in blk and _window_of(blk)), 0)
        return {"live": jnp.stack([
            jnp.sum(jnp.where(valid, live, 0)), jnp.sum(valid),
            jnp.sum(jnp.where(valid, jnp.minimum(live, window), 0))])}

    def _keep(self, i, names, leaves):
        """This block's entries of the new cache: ``leaves`` under
        ``names``, nothing under the cache's other names."""
        for name in self.new:
            if name != "pos":
                self.new[name].append(None)
        for name, leaf in zip(names, leaves):
            self.new[name][i] = leaf

    def mix(self, i, blk, h):
        """Block ``i``'s mixer over its normed input ``h`` (B, d)."""
        dtype = self.dtype
        mixer = _mixer(blk)
        if mixer:
            kind, module, names = mixer
            # the module's first `whole` leaves go in and come out whole:
            # its step reads and writes the stepped rows where they lie
            whole = module.STEPS_IN_PLACE
            step = (functools.partial(module.mix_step, rows=self.rows)
                    if whole else module.mix_step)
            with jax.named_scope(kind):
                pools = [self.cache[name][i] for name in names]
                with jax.named_scope("gather"):
                    state = pools[:whole] + [
                        _pool_rows(pool, self.rows).reshape(-1, *shape)
                        for pool, shape in zip(
                            pools[whole:],
                            module.state_shapes(blk[kind])[whole:])]
                out, *state = step(blk[kind], h, *state, dtype)
                if kind == "ssm":  # its scan output, for the memory units
                    self.memory, *state = state
                with jax.named_scope("scatter"):
                    # the whole state of the stepped rows, and of no other
                    self._keep(i, names, state[:whole] + [
                        pool.at[self.rows].set(new.reshape(
                            -1, *pool.shape[1:]).astype(pool.dtype))
                        for pool, new in zip(pools[whole:], state[whole:])])
            return out
        if "gmu" in blk:
            self._keep(i, (), ())
            with jax.named_scope("gmu"):
                return mamba.gmu(blk["gmu"], h, self.memory, dtype)
        with jax.named_scope("attn"):
            if "diff" in blk:
                q = diffattn.project_q(blk, h, dtype)
            else:  # plain attention: the three projections at once
                q, *fresh = _plain_qkv(blk, h, dtype, self.pos)
            if "wk" not in blk:
                self._keep(i, (), ())
            else:
                pools = self.cache["k"][i], self.cache["v"][i]
                slot = self.pos % pools[0].shape[1]
                with jax.named_scope("scatter"):
                    if "diff" in blk:
                        fresh = diffattn.project_kv(blk, h, dtype)
                    pools = [pool.at[self.rows, slot].set(new.reshape(
                        -1, pool.shape[-1]).astype(pool.dtype))
                        for pool, new in zip(pools, fresh)]
                self._keep(i, ("k", "v"), pools)
                if self.slots is not None:
                    # write first, then read (see _decode); the cross
                    # layers after this one read the same gathered rows
                    with jax.named_scope("gather"):
                        pools = [_pool_rows(pool, self.slots)
                                 for pool in pools]
                self.kv_rows = pools
            if "diff" not in blk:
                # a window layer's ring holds its window: the ring's mask is
                # the window's
                with jax.named_scope("window" if _window_of(blk) else "full"):
                    return _plain_out(blk, _attend_rows(
                        q, *self.kv_rows, self.pos, dtype,
                        _scale_of(blk, q.shape[-1])), dtype)
            with jax.named_scope(_diff_kind(blk)):
                return diffattn.attend_one(blk, q, *self.kv_rows, self.pos,
                                           dtype)


def _mla_step(blk, pool, sink, x, pos, rows, slots, dtype):
    """A latent block's attention at one position a row: project, write
    the position's row into ``pool`` at its ring slot (the written pool
    goes to ``sink``), read the stepped rows back (``slots``) and attend
    absorbed.  ``pos`` is a scalar or one position a row."""
    b = x.shape[0]
    pos = jnp.broadcast_to(pos, (b,))
    if rows is None:
        rows = jnp.arange(b)
    h = _pre(blk, "ln1", x)
    q_nope, q_pe, row = mla.project(
        blk["mla"], h, *mla.rope(blk["mla"], pos), dtype)
    with jax.named_scope("scatter"):
        pool = pool.at[rows, pos % pool.shape[1]].set(row.astype(pool.dtype))
    sink.append(pool)
    if slots is not None:
        with jax.named_scope("gather"):
            pool = _pool_rows(pool, slots)
    return mla.attend_absorbed(blk["mla"], q_nope, q_pe, pool, pos, dtype)


def prefill(params, cache, prefix, rows=None, *,
            compute_dtype=jnp.bfloat16, window=None, last_only=False,
            **moe):
    """Admit the prefixes ``(B, T0, obs_dim)`` (``(B, T0)`` ids for a
    token model) into ``cache`` with ONE teacher-forced pass (the
    standard prefill/decode split) instead of T0 serial
    :func:`decode_step`s: returns ``(predictions, cache)``, the
    predictions ``(B, T0, ...)`` float32 (``last_only``: position T0's
    alone, ``(B, 1, ...)``), the cache holding the bytes serial decode
    would have written (k/v are rotated before the sink; a latent model
    attends expanded and sinks its latent rows; a recurrent layer
    goes on from the row's own recurrent state, which
    :func:`rewind_rows` has zeroed, and writes the state after T0 whole)
    with ``pos`` at T0.

    ``rows=None`` fills every row of the cache (which then has ``B`` of
    them: what :func:`rollout` does); ``rows`` ``(B,)`` names the rows of
    a per-row cache LARGER than the batch (the serving tier's slot pool)
    that prefix ``j`` goes to.  Every other row, and every position of
    a named row that is not written, is the input's, so a caller that
    donates the cache has it updated in place.  A prefix longer than the
    ring keeps only the tail that fits, placed at each position's ring
    slot (distinct, since at most ``C`` consecutive ones are kept).
    ``moe`` are the legacy expert layer's ``moe_*`` arguments, as
    :func:`apply` takes them."""
    hybrid = _hybrid(params)
    _windows_are_described(hybrid, window)
    kvs, more = [], {}
    t0 = prefix.shape[1]
    if hybrid:
        # the recurrent layers go on from the rows' own state, which a
        # rewind has zeroed: the prefill is T0 decode steps of a rewound row
        more["state_in"] = {
            name: [leaf if leaf is None or rows is None
                   else _pool_rows(leaf, rows) for leaf in cache[name]]
            for name in _RECURRENT if name in cache}
        if t0 % 32 == 0:
            more["diff_attn_fn"] = _flash_diff_attn
    with jax.named_scope("forward"):
        preds, _ = _forward(
            params, prefix,
            lambda q, k, v: full_attention(q, k, v, causal=True,
                                           window=window),
            compute_dtype, kv_sink=kvs, last_only=last_only, **more, **moe)
    # each ring keeps the last positions that fit (one length per model,
    # or a window layer's ring beside the full layer's)
    rings = {}
    for i, kept in enumerate(kvs):
        for name in kept:
            if name in _RECURRENT:
                continue
            ring = cache[name][i].shape[1]
            if ring not in rings:
                keep_n = min(t0, ring)
                rings[ring] = keep_n, (
                    jnp.arange(keep_n) + (t0 - keep_n)) % ring
    with jax.named_scope("scatter"):
        if rows is None:
            new = {"pos": jnp.full_like(cache["pos"], t0)}
        else:
            new = {"pos": cache["pos"].at[rows].set(t0)}
        for name in cache:
            if name != "pos":
                new[name] = list(cache[name])
        for i, kept in enumerate(kvs):
            for name, t in kept.items():
                pool = cache[name][i]
                if name in _RECURRENT:  # no positions: written whole
                    t = t.reshape(-1, *pool.shape[1:]).astype(pool.dtype)
                    pool = t if rows is None else pool.at[rows].set(t)
                else:
                    keep_n, slots_ax = rings[pool.shape[1]]
                    if rows is None:
                        pool = pool.at[:, slots_ax].set(
                            t[:, t0 - keep_n:].astype(pool.dtype))
                    else:
                        for j in range(t.shape[0]):
                            pool = pool.at[rows[j], slots_ax].set(
                                t[j, t0 - keep_n:].astype(pool.dtype))
                new[name][i] = pool
    return preds, new


def _flash_diff_attn(q, k, v, scale, window):
    """The flash kernel under a differential block's two maps, at the
    tile policy's choice for the sequence (a multiple of 32 long)."""
    from blendjax.ops.flash_attention import flash_attention, flash_block_size

    t = q.shape[1]
    window = None if window is None or window >= t else window
    block = flash_block_size(t, q.shape[-1], q.dtype, window)
    return flash_attention(q, k, v, causal=True, scale=scale, block_q=block,
                           block_kv=block, window=window)


def rewind_rows(cache, rows):
    """``cache`` (per-row) with ``rows`` rewound to position 0, ready for
    their next tenants.  For what positions index, rewinding ``pos`` is
    sufficient: :func:`_attn_one` masks by each slot's absolute position,
    so the stale k/v (or latent) rows of the previous tenant sit at
    negative positions and never attend.  A recurrent state cannot be
    masked: the rows' entries under :data:`_MIXERS`' names are zeroed."""
    new = {**cache, "pos": cache["pos"].at[rows].set(0)}
    for name in _RECURRENT:
        if name in cache:
            new[name] = [leaf if leaf is None else leaf.at[rows].set(0)
                         for leaf in cache[name]]
    return new


def rollout(params, prefix, n_steps, compute_dtype=jnp.bfloat16,
            moe_impl="dense", moe_k=2, moe_capacity_factor=1.25,
            moe_dispatch="sort", window=None, cache_dtype=None):
    """Autoregressive world-model rollout ("dreaming"): consume the
    ``prefix`` episode (B, T0, obs_dim), then feed the model its own
    next-observation predictions for ``n_steps`` more steps.

    Returns (B, n_steps, obs_dim) float32 predictions for positions
    T0 .. T0+n_steps-1.  Incremental per-step cost is O(cache) attention
    over the KV cache instead of re-running the O(T^2) forward on the
    growing sequence; under a ``window`` the cache is a RING BUFFER of
    ``window`` slots, so memory stays O(window) however long the dream
    (with ``pos_encoding='rope'`` the horizon is then bounded only by
    rope's f32 angle precision).  Parity with the naive re-run is
    tested.  Jit-compatible (the phases are one teacher-forced pass and
    a ``lax.scan``).

    The reference has no sequence models, let alone an inference path
    (SURVEY.md §5); this completes the world-model workload the
    framework adds.
    """
    if "table" in params["embed"]:
        raise ValueError(
            "rollout() feeds a model its own predictions; a token model "
            "answers with logits and nothing here samples an id from them "
            "(ROADMAP M5): serve it (blendjax.serve) or drive prefill() and "
            "decode_step() yourself")
    b, t0, obs_dim = prefix.shape
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    if t0 < 1:
        raise ValueError("prefix must contain at least one observation")
    if "pos" in params and t0 + n_steps > params["pos"].shape[0]:
        # rope models ("pos" absent) have no table and no length bound
        raise ValueError(
            f"prefix {t0} + rollout {n_steps} exceeds max_len "
            f"{params['pos'].shape[0]}"
        )
    from jax import lax

    # drop-free MoE routing on BOTH phases (see _drop_free): routing
    # must be per-token independent for the vectorized prefill and the
    # incremental decode to agree
    moe = dict(moe_impl=moe_impl, moe_k=moe_k, moe_dispatch=moe_dispatch,
               moe_capacity_factor=_drop_free(params, moe_k,
                                              moe_capacity_factor))
    total = t0 + n_steps
    # windowed: a ring buffer of `window` slots bounds memory at
    # O(window) no matter the horizon (decode_step writes at pos % C,
    # _attn_one masks by slot position)
    cache = init_cache(params, b, dtype=cache_dtype or compute_dtype,
                       length=total if window is None else min(total, window))
    preds, cache = prefill(params, cache, prefix,
                           compute_dtype=compute_dtype, window=window, **moe)
    last_pred = preds[:, -1]  # prediction for position t0

    def dream(carry, _):
        cache, obs_t = carry
        pred, cache = decode_step(params, cache, obs_t,
                                  compute_dtype=compute_dtype, window=window,
                                  **moe)
        return (cache, pred), obs_t

    (_, final), dreamed = lax.scan(
        dream, (cache, last_pred), None, length=n_steps - 1
    )
    out = jnp.concatenate([dreamed, final[None]], axis=0)
    return out.transpose(1, 0, 2)
