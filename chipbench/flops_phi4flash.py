"""Required operations and bytes of the `phi4flash` decoder-hybrid-decoder
model's decode step, from shapes.

"Required" is what the mathematics needs, whatever implements it: every
weight a tick uses read once (the tied embedding once, as the head), one
embedding row a step, each stepped row's **live** positions of the one
full-length K/V once for each of its readers (the full layer and the cross
layers), the live positions of each window ring, each recurrent state read
and written once.  Plain arithmetic on the configuration's published keys
and its state-space sizes; nothing imports the program.
"""

from __future__ import annotations


def layer_kinds(model):
    """``"ssm"``, ``"window"``, ``"full"``, ``"gmu"`` or ``"cross"`` for
    every layer, from ``num_hidden_layers`` and ``mb_per_layer``
    (arXiv:2507.06607): in the first half every ``mb_per_layer``-th layer
    is state-space and the others window attention; the second half opens
    with a state-space layer and a full-attention layer; then gated memory
    units (every ``mb_per_layer``-th) and cross attention alternate.  The
    benchmark's own copy of the rule (the reference reads it from here;
    the program has its own)."""
    n, every = model["num_hidden_layers"], model["mb_per_layer"]
    half = n // 2
    kinds = []
    for layer in range(n):
        second = layer > half + 1
        state = layer % every == 0 or layer == half
        if layer == half + 1:
            kinds.append("full")
        elif state:
            kinds.append("gmu" if second else "ssm")
        else:
            kinds.append("cross" if second else "window")
    return kinds


def layer_counts(model):
    """How many layers of each kind."""
    kinds = layer_kinds(model)
    return {kind: kinds.count(kind)
            for kind in ("ssm", "window", "full", "gmu", "cross")}


def weight_counts(model):
    """Parameters of one layer's parts, the embedding and the norms."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv, dh = model["num_key_value_heads"], model["hidden_size"] // heads
    di, n = model["mamba_expand"] * d, model["mamba_d_state"]
    conv, rank = model["mamba_d_conv"], model["mamba_dt_rank"]
    diff = 4 * dh + 2 * dh  # the four lambda vectors, the sub-norm's scale
    return {
        "mlp": 3 * d * model["intermediate_size"],
        "ssm": (d * 2 * di + di * (rank + 2 * n) + rank * di + di * d
                + conv * di + di + di + n * di + di),
        "ssm_vectors": conv * di + di + di + n * di + di,
        "attention": 2 * d * heads * dh + 2 * d * kv * dh + diff,
        "cross": 2 * d * heads * dh + diff,
        "gmu": 2 * d * di,
        "embed": model["vocab_size"] * d,
        "norms": (2 * model["num_hidden_layers"] + 1) * 2 * d,
        "diff": diff,
    }


def param_count(model):
    """Every parameter of the model (the embedding is tied: once)."""
    w, k = weight_counts(model), layer_counts(model)
    return (model["num_hidden_layers"] * w["mlp"] + k["ssm"] * w["ssm"]
            + (k["window"] + k["full"]) * w["attention"]
            + k["gmu"] * w["gmu"] + k["cross"] * w["cross"]
            + w["embed"] + w["norms"])


def slot_bytes(model, length, cache_bytes=2, state_bytes=4):
    """Bytes of one slot of the pool, by kind: the window rings, the one
    full-length K/V, the recurrent state (``h`` in ``state_bytes``, the
    convolution tail in ``cache_bytes``)."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    row = 2 * model["num_key_value_heads"] * (d // heads) * cache_bytes
    di = model["mamba_expand"] * d
    k = layer_counts(model)
    return {
        "rings": k["window"] * min(model["sliding_window"], length) * row,
        "full": k["full"] * length * row,
        "state": k["ssm"] * (model["mamba_d_state"] * di * state_bytes
                             + (model["mamba_d_conv"] - 1) * di
                             * cache_bytes),
    }


def decode_flops(model, n_steps, ctx_positions, window_positions):
    """``n_steps`` decode steps whose live positions of the full K/V add
    up to ``ctx_positions`` and of one window ring to
    ``window_positions``: every matrix once a step, per live position
    and attention layer two score maps over ``Dh`` and two weighted sums
    over ``2 Dh`` for each query pair, the state's update."""
    w, k = weight_counts(model), layer_counts(model)
    heads = model["num_attention_heads"]
    dh = model["hidden_size"] // heads
    matrices = (param_count(model) - w["norms"] - k["ssm"] * w["ssm_vectors"]
                - (k["window"] + k["full"] + k["cross"]) * w["diff"])
    per_pos = (heads // 2) * 2 * (2.0 * dh + 2.0 * 2 * dh)
    di, n = model["mamba_expand"] * model["hidden_size"], model["mamba_d_state"]
    return (float(n_steps) * (2.0 * matrices + k["ssm"] * 6.0 * di * n)
            + per_pos * ((k["full"] + k["cross"]) * float(ctx_positions)
                         + k["window"] * float(window_positions)))


def decode_bytes(model, n_ticks, n_steps, ctx_positions, window_positions,
                 param_bytes=2, cache_bytes=2, state_bytes=4):
    """Bytes the decode ticks have to move: every parameter once a tick,
    one embedding row a step, each stepped row's live full-length K/V
    positions once a reader, its live ring positions once a ring, its
    recurrent state read and written once."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    row = 2 * model["num_key_value_heads"] * (d // heads) * cache_bytes
    k = layer_counts(model)
    state = slot_bytes(model, 1, cache_bytes, state_bytes)["state"]
    return (float(n_ticks) * param_count(model) * param_bytes
            + float(n_steps) * (d * param_bytes + 2 * state)
            + row * ((k["full"] + k["cross"]) * float(ctx_positions)
                     + k["window"] * float(window_positions)))
