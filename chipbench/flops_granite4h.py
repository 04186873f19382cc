"""Required operations and bytes of the `granitemoehybrid` model's decode
step (granite-4.0-h-micro: Mamba-2 layers beside NoPE grouped-query
attention, no experts), from shapes.

"Required" is what the mathematics needs, whatever implements it: every
weight of the layers and the tied table (as the head) read once a tick,
one embedding row a step, each stepped row's recurrent state (the float32
matrix state a head and the convolution tail) read and written once, each
stepped row's **live** positions of every attention layer's K/V once.
Plain arithmetic on the configuration's published keys; nothing imports
the program.
"""

from __future__ import annotations

_KINDS = {"mamba": "mamba", "attention": "attention"}


def layer_kinds(model):
    """``"mamba"`` or ``"attention"`` for every layer: the configuration's
    own ``layer_types``, its first ``num_hidden_layers`` entries.  The
    benchmark's own reading of the list (the reference reads it from
    here; the program has its own)."""
    types = model["layer_types"][:model["num_hidden_layers"]]
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("fewer layer_types than layers")
    return [_KINDS[t] for t in types]


def layer_counts(model):
    kinds = layer_kinds(model)
    return {kind: kinds.count(kind) for kind in _KINDS.values()}


def sizes(model):
    """``(d, heads, kv heads, Dh, H, P, N, taps)``: the attention layers'
    and the Mamba-2 layers' widths (one group of B and C)."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    if model["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C")
    return (d, heads, model["num_key_value_heads"], d // heads,
            model["mamba_n_heads"], model["mamba_d_head"],
            model["mamba_d_state"], model["mamba_d_conv"])


def weight_counts(model):
    """Parameters of one layer's parts, the embedding, the head, and the
    vectors among them (what no matrix product reads)."""
    d, heads, kv, dh, h, p, n, taps = sizes(model)
    d_inner, conv = h * p, h * p + 2 * n
    mamba_vectors = taps * conv + conv + 3 * h + d_inner
    return {
        "mlp": 3 * d * model["shared_intermediate_size"],
        "mamba": d * (2 * d_inner + 2 * n + h) + d_inner * d + mamba_vectors,
        "mamba_vectors": mamba_vectors,   # taps, bias, dt_bias, A, D, norm
        "attention": 2 * d * heads * dh + 2 * d * kv * dh,
        "norms": 2 * model["num_hidden_layers"] * d + d,
        "embed": model["vocab_size"] * d,
        "head": 0 if model.get("tie_word_embeddings")
        else model["vocab_size"] * d,
    }


def layer_params(model):
    """Every parameter of the layers and the final norm (no embedding, no
    head)."""
    w, k = weight_counts(model), layer_counts(model)
    return (model["num_hidden_layers"] * w["mlp"] + k["mamba"] * w["mamba"]
            + k["attention"] * w["attention"] + w["norms"])


def param_count(model):
    w = weight_counts(model)
    return layer_params(model) + w["embed"] + w["head"]


def slot_bytes(model, length, cache_bytes=2, state_bytes=4):
    """Bytes of one slot of the pool, by kind: the Mamba-2 layers' matrix
    states (``state_bytes`` a number), their convolution tails and the
    attention layers' K/V (``cache_bytes``)."""
    _, _, kv, dh, h, p, n, taps = sizes(model)
    k = layer_counts(model)
    return {
        "state": k["mamba"] * h * p * n * state_bytes,
        "tails": k["mamba"] * (taps - 1) * (h * p + 2 * n) * cache_bytes,
        "kv": k["attention"] * length * 2 * kv * dh * cache_bytes,
    }


def state_row_bytes(model, cache_bytes=2, state_bytes=4):
    """Recurrent state and tail behind one slot: what a step reads, and
    writes again, of each row (the program's ``serve_state_bytes`` counts
    twice this a real row stepped)."""
    slot = slot_bytes(model, 0, cache_bytes, state_bytes)
    return slot["state"] + slot["tails"]


def decode_flops(model, n_steps, ctx_positions):
    """``n_steps`` decode steps whose live positions of one attention
    layer's K/V add up to ``ctx_positions``: every matrix once a step
    (the tied table as the head), the Mamba-2 update's ``5 P N`` a head
    (the decay's multiply, the rank-one write's multiply and add, the
    read's multiply and add), and per live position and attention layer
    a score and a weighted sum over ``Dh`` for each query head."""
    _, heads, _, dh, h, p, n, _ = sizes(model)
    w, k = weight_counts(model), layer_counts(model)
    matrices = (layer_params(model) - w["norms"]
                - k["mamba"] * w["mamba_vectors"]
                + (w["head"] or w["embed"]))
    return (float(n_steps) * (2.0 * matrices + k["mamba"] * 5.0 * h * p * n)
            + k["attention"] * float(ctx_positions) * heads * 4.0 * dh)


def decode_bytes(model, n_ticks, n_steps, ctx_positions, state_bytes=None,
                 param_bytes=2, cache_bytes=2):
    """Bytes the decode ticks have to move: every weight of the layers
    and the head once a tick, one embedding row a step, the recurrent
    state the stepped rows read and wrote (``state_bytes``: the program's
    counter, or twice :func:`state_row_bytes` a step), each stepped row's
    live K/V positions once an attention layer."""
    d, _, kv, dh, _, _, _, _ = sizes(model)
    w, k = weight_counts(model), layer_counts(model)
    if state_bytes is None:
        state_bytes = 2.0 * n_steps * state_row_bytes(model, cache_bytes)
    return (float(n_ticks) * (layer_params(model)
                              + (w["head"] or w["embed"])) * param_bytes
            + float(n_steps) * d * param_bytes + float(state_bytes)
            + k["attention"] * float(ctx_positions) * 2 * kv * dh
            * cache_bytes)
