"""Required operations and bytes of the `olmo_hybrid` model's decode step,
from shapes.

"Required" is what the mathematics needs, whatever implements it: every
weight of the layers and the head read once a tick, one embedding row a
step, each stepped row's recurrent state (the float32 matrix state a head
and the convolution tails) read and written once, each stepped row's
**live** positions of every full layer's K/V once.  Plain arithmetic on the
configuration's published keys; nothing imports the program.
"""

from __future__ import annotations

_KINDS = {"linear_attention": "linear", "full_attention": "full"}


def layer_kinds(model):
    """``"linear"`` or ``"full"`` for every layer: the configuration's own
    ``layer_types``, its first ``num_hidden_layers`` entries.  The
    benchmark's own reading of the list (the reference reads it from
    here; the program has its own)."""
    types = model["layer_types"][:model["num_hidden_layers"]]
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("fewer layer_types than layers")
    return [_KINDS[t] for t in types]


def layer_counts(model):
    kinds = layer_kinds(model)
    return {kind: kinds.count(kind) for kind in ("linear", "full")}


def _sizes(model):
    d, heads = model["hidden_size"], model["num_attention_heads"]
    lin = model["linear_num_key_heads"]
    return (d, heads, model["num_key_value_heads"], d // heads, lin,
            model["linear_key_head_dim"], model["linear_value_head_dim"],
            model["linear_conv_kernel_dim"])


def weight_counts(model):
    """Parameters of one layer's parts, the embedding, the head, and the
    vectors among them (what no matrix product reads)."""
    d, heads, kv, dh, lin, dk, dv, taps = _sizes(model)
    conv = taps * (2 * lin * dk + lin * dv)
    linear_vectors = conv + 2 * lin + dv   # taps, a_log, dt_bias, o_norm
    full_vectors = heads * dh + kv * dh    # the q and k norms
    return {
        "mlp": 3 * d * model["intermediate_size"],
        "linear": (2 * d * lin * dk + 3 * d * lin * dv + 2 * d * lin
                   + linear_vectors),
        "linear_vectors": linear_vectors,
        "full": 2 * d * heads * dh + 2 * d * kv * dh + full_vectors,
        "full_vectors": full_vectors,
        "norms": 2 * model["num_hidden_layers"] * d + d,
        "embed": model["vocab_size"] * d,
        "head": 0 if model.get("tie_word_embeddings")
        else model["vocab_size"] * d,
    }


def layer_params(model):
    """Every parameter of the layers and the final norm (no embedding, no
    head)."""
    w, k = weight_counts(model), layer_counts(model)
    return (model["num_hidden_layers"] * w["mlp"] + k["linear"] * w["linear"]
            + k["full"] * w["full"] + w["norms"])


def param_count(model):
    w = weight_counts(model)
    return layer_params(model) + w["embed"] + w["head"]


def slot_bytes(model, length, cache_bytes=2, state_bytes=4):
    """Bytes of one slot of the pool, by kind: the linear layers' matrix
    states (``state_bytes`` a number), their three convolution tails and
    the full layers' K/V (``cache_bytes``)."""
    _, _, kv, dh, lin, dk, dv, taps = _sizes(model)
    k = layer_counts(model)
    return {
        "state": k["linear"] * lin * dv * dk * state_bytes,
        "tails": k["linear"] * (taps - 1) * (2 * lin * dk + lin * dv)
        * cache_bytes,
        "kv": k["full"] * length * 2 * kv * dh * cache_bytes,
    }


def state_row_bytes(model, cache_bytes=2, state_bytes=4):
    """Recurrent state and tails behind one slot: what a step reads, and
    writes again, of each row (the program's ``serve_state_bytes`` counts
    twice this a real row stepped)."""
    slot = slot_bytes(model, 0, cache_bytes, state_bytes)
    return slot["state"] + slot["tails"]


def decode_flops(model, n_steps, ctx_positions):
    """``n_steps`` decode steps whose live positions of one full layer's
    K/V add up to ``ctx_positions``: every matrix once a step, the delta
    rule's ``6 dk dv`` a head (the state times the key, the rank-one
    update, the state times the query, a multiply and an add each), and
    per live position and full layer a score and a weighted sum over
    ``Dh`` for each head."""
    d, heads, _, dh, lin, dk, dv, _ = _sizes(model)
    w, k = weight_counts(model), layer_counts(model)
    matrices = (layer_params(model) - w["norms"]
                - k["linear"] * w["linear_vectors"]
                - k["full"] * w["full_vectors"]
                + (w["head"] or w["embed"]))
    return (float(n_steps) * (2.0 * matrices
                              + k["linear"] * 6.0 * lin * dk * dv)
            + k["full"] * float(ctx_positions) * heads * 4.0 * dh)


def decode_bytes(model, n_ticks, n_steps, ctx_positions, state_bytes=None,
                 param_bytes=2, cache_bytes=2):
    """Bytes the decode ticks have to move: every weight of the layers
    and the head once a tick, one embedding row a step, the recurrent
    state the stepped rows read and wrote (``state_bytes``: the program's
    counter, or twice :func:`state_row_bytes` a step), each stepped row's
    live K/V positions once a full layer."""
    d, _, kv, dh, _, _, _, _ = _sizes(model)
    w, k = weight_counts(model), layer_counts(model)
    if state_bytes is None:
        state_bytes = 2.0 * n_steps * state_row_bytes(model, cache_bytes)
    return (float(n_ticks) * (layer_params(model)
                              + (w["head"] or w["embed"])) * param_bytes
            + float(n_steps) * d * param_bytes + float(state_bytes)
            + k["full"] * float(ctx_positions) * 2 * kv * dh * cache_bytes)
