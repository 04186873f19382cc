"""Required operations and bytes of the `sarvam_mla` token model's served
share, from shapes.

"Required" is what the mathematics needs, whatever implements it: absorbed
attention over each row's live positions only, the experts a token was
routed to and this chip holds, every weight a tick uses read once, the held
experts that got a token read once (the count comes from the program's
counter, so it is the same whatever implements the grouped product).  Plain
arithmetic on the configuration's published keys; nothing imports the
program.
"""

from __future__ import annotations


def weight_counts(model):
    """Parameters by part (matrices only; the norms' scales are counted
    under ``norms``)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope, v = model["qk_rope_head_dim"], model["v_head_dim"]
    layers = model["num_hidden_layers"]
    dense_layers = model["first_k_dense_replace"]
    return {
        "attention": (d * h * (nope + rope) + d * (rank + rope)
                      + rank * h * (nope + v) + h * v * d),
        "dense_mlp": 3 * d * model["intermediate_size"],
        "expert": 3 * d * model["moe_intermediate_size"],
        "router": d * model["num_experts"] + model["num_experts"],
        "shared": (model.get("num_shared_experts", 0) * 3 * d
                   * model["moe_intermediate_size"]),
        "embed": model["vocab_size"] * d,
        "head": d * model["vocab_size"],
        "norms": layers * (2 * d + rank) + d,
        "layers": layers,
        "dense_layers": dense_layers,
        "expert_layers": layers - dense_layers,
        "held": model["num_experts_held"],
    }


def param_count(model):
    """Every parameter this chip holds."""
    w = weight_counts(model)
    return (w["layers"] * w["attention"] + w["dense_layers"] * w["dense_mlp"]
            + w["expert_layers"] * (w["held"] * w["expert"] + w["shared"]
                                    + w["router"])
            + w["embed"] + w["head"] + w["norms"])


def decode_flops(model, n_steps, sum_pos, experts_per_token):
    """``n_steps`` absorbed decode steps whose live positions (the one
    each step is taken at included) add up to ``sum_pos``;
    ``experts_per_token`` is the routed experts computed here for a token
    of an expert layer (from the counters), the shared one beside them."""
    w = weight_counts(model)
    d, h = model["hidden_size"], model["num_attention_heads"]
    rank, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope, v = model["qk_rope_head_dim"], model["v_head_dim"]
    # per token and layer: W_q, W_dkv, the query's absorption, the value's
    # expansion, W_o; per live position: scores over rank + rope, the
    # weighted sum over rank
    per_token = 2.0 * (d * h * (nope + rope) + d * (rank + rope)
                       + h * nope * rank + h * rank * v + h * v * d)
    per_pos = 2.0 * h * (2 * rank + rope)
    experts = (experts_per_token + model.get("num_shared_experts", 0)) \
        * 2.0 * w["expert"] + 2.0 * model["hidden_size"] * model["num_experts"]
    return (n_steps * (w["layers"] * per_token
                       + w["dense_layers"] * 2.0 * w["dense_mlp"]
                       + w["expert_layers"] * experts + 2.0 * w["head"])
            + w["layers"] * per_pos * float(sum_pos))


def decode_bytes(model, n_ticks, n_steps, sum_pos, experts_hit,
                 param_bytes=2, cache_bytes=2):
    """Bytes the decode ticks have to read: every weight a tick uses once
    a tick (attention, the dense MLP, routers, shared experts, the head,
    the norms), each held expert that got a token once for that tick
    (``experts_hit``: summed over layers and ticks), one embedding row a
    step, and each stepped row's live latent positions."""
    w = weight_counts(model)
    every_tick = (w["layers"] * w["attention"]
                  + w["dense_layers"] * w["dense_mlp"]
                  + w["expert_layers"] * (w["shared"] + w["router"])
                  + w["head"] + w["norms"])
    row = (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * cache_bytes
    return (float(n_ticks) * every_tick * param_bytes
            + float(experts_hit) * w["expert"] * param_bytes
            + float(n_steps) * model["hidden_size"] * param_bytes
            + float(sum_pos) * w["layers"] * row)
