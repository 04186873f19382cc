"""Required operations and bytes of the `mellum` model's served share
(Mellum2-12B-A2.5B: grouped-query attention, three sliding-window layers to
every full layer, softmax-routed experts held 16 of 64), from shapes.

"Required" is what the mathematics needs, whatever implements it: every
weight outside the experts read once a tick (attention, q/k norms, routers,
the layer norms and the head), each held expert that got a token read once
for that tick (the count comes from the program's counter), one embedding
row a step, and each stepped row's **live** K/V positions once a layer: a
window layer's ring holds ``min(pos + 1, window)`` of them (the program's
``serve_window_positions``), a full layer's ``pos + 1``
(``serve_ctx_positions``).  Plain arithmetic on the configuration's
published keys; nothing imports the program.
"""

from __future__ import annotations

_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_kinds(model):
    """``"window"`` or ``"full"`` for every layer: the configuration's own
    ``layer_types``, its first ``num_hidden_layers`` entries (the
    benchmark's own reading of the list; the program has its own)."""
    types = model["layer_types"][:model["num_hidden_layers"]]
    if len(types) != model["num_hidden_layers"]:
        raise ValueError("fewer layer_types than layers")
    return [_KINDS[t] for t in types]


def layer_counts(model):
    kinds = layer_kinds(model)
    return {kind: kinds.count(kind) for kind in ("window", "full")}


def weight_counts(model):
    """Parameters by part: one layer's attention (its four projections and
    the two per-head norms), one expert, one router, the layer norms, the
    embedding and the head."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv, dh = model["num_key_value_heads"], model["head_dim"]
    return {
        "attention": 2 * d * heads * dh + 2 * d * kv * dh + 2 * dh,
        "attention_vectors": 2 * dh,
        "expert": 3 * d * model["moe_intermediate_size"],
        "router": d * model["num_experts"],
        "norms": 2 * model["num_hidden_layers"] * d + d,
        "embed": model["vocab_size"] * d,
        "head": model["vocab_size"] * d,
        "held": model["num_experts_held"],
        "layers": model["num_hidden_layers"],
    }


def param_count(model):
    """Every parameter this chip holds."""
    w = weight_counts(model)
    return (w["layers"] * (w["attention"] + w["router"]
                           + w["held"] * w["expert"])
            + w["norms"] + w["embed"] + w["head"])


def tick_weights(model):
    """Parameters every tick reads whatever its rows: all but the experts
    and the embedding."""
    w = weight_counts(model)
    return w["layers"] * (w["attention"] + w["router"]) + w["norms"] \
        + w["head"]


def kv_position_bytes(model, cache_bytes=2):
    """Bytes of one position of one layer's K and V."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * cache_bytes


def slot_bytes(model, length, cache_bytes=2):
    """Bytes of one slot of the pool: a ring of ``sliding_window``
    positions per window layer, ``length`` per full layer."""
    k = layer_counts(model)
    ring = min(model["sliding_window"], length)
    return (k["window"] * ring + k["full"] * length) \
        * kv_position_bytes(model, cache_bytes)


def kv_bytes(model, window_positions, ctx_positions, cache_bytes=2):
    """Live K/V the stepped rows read: every window layer's ring positions
    (``window_positions`` a ring, summed over rows and ticks), every full
    layer's ``ctx_positions``."""
    k = layer_counts(model)
    return (k["window"] * float(window_positions)
            + k["full"] * float(ctx_positions)) \
        * kv_position_bytes(model, cache_bytes)


def decode_bytes(model, n_ticks, n_steps, experts_hit, window_positions,
                 ctx_positions, param_bytes=2, cache_bytes=2):
    """Bytes the decode ticks have to move: every weight outside the
    experts once a tick (the router at its float32 width), each held
    expert that got a token once for that tick (``experts_hit``: summed
    over layers and ticks), one embedding row a step, and the live K/V
    (:func:`kv_bytes`)."""
    w = weight_counts(model)
    router_bytes = 4
    per_tick = ((tick_weights(model) - w["layers"] * w["router"])
                * param_bytes + w["layers"] * w["router"] * router_bytes)
    return (float(n_ticks) * per_tick
            + float(experts_hit) * w["expert"] * param_bytes
            + float(n_steps) * model["hidden_size"] * param_bytes
            + kv_bytes(model, window_positions, ctx_positions, cache_bytes))


def decode_flops(model, n_steps, experts_per_token, window_positions,
                 ctx_positions):
    """``n_steps`` decode steps: every matrix outside the experts once a
    step (four projections, the router, the head), ``experts_per_token``
    held experts a token and layer (from the counters), and per live
    position and layer a score and a weighted sum over ``head_dim`` for
    every query head."""
    w = weight_counts(model)
    k = layer_counts(model)
    heads, dh = model["num_attention_heads"], model["head_dim"]
    matrices = w["layers"] * (w["attention"] - w["attention_vectors"]
                              + w["router"]) + w["head"]
    return (float(n_steps) * (2.0 * matrices + w["layers"]
                              * experts_per_token * 2.0 * w["expert"])
            + (k["window"] * float(window_positions)
               + k["full"] * float(ctx_positions)) * heads * 4.0 * dh)
