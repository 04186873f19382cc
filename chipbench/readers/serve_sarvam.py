"""Readers of the routed latent-attention serve cell: the whole decode
step's shares of the chip's peaks, and the load of an expert.  They read the
program's three counters (``serve_moe_assignments``,
``serve_moe_assignments_held``, ``serve_moe_experts_hit``) among the
window's ``events``; a program that does not count them reads None and the
metric is left out.
"""

from __future__ import annotations

from chipbench import flops_sarvam

_WIDTH = {"float32": 4, "bfloat16": 2}


def _counts(obs):
    events = obs.get("events") or {}
    made = events.get("serve_moe_assignments")
    held = events.get("serve_moe_assignments_held")
    hit = events.get("serve_moe_experts_hit")
    ticks = events.get("serve_batches")
    if not made or not held or not hit or not ticks:
        return None
    return made, held, hit, ticks


def moe_mla_decode_mfu_pct(obs, ctx):
    """Required FLOPs of the steps answered in the window (absorbed
    attention over each row's live positions; the routed experts computed
    here per token by the counters, the shared one beside them) over the
    window and the chip's bf16 peak."""
    counts, n = _counts(obs), obs.get("replies_in_window")
    if not counts or not n or not obs.get("window_s"):
        return None
    made, held, _, _ = counts
    per_token = ctx.config["num_experts_per_tok"] * held / made
    need = flops_sarvam.decode_flops(ctx.config, n,
                                     obs["sum_pos_in_window"], per_token)
    return 100.0 * need / obs["window_s"] / ctx.peaks["bf16_flops_per_s"]


def moe_mla_decode_hbm_pct(obs, ctx):
    """Bytes the window's ticks had to read (the held experts that got a
    token, by the counter; every other weight once a tick; the live latent
    rows) over the window and the chip's HBM peak."""
    counts, n = _counts(obs), obs.get("replies_in_window")
    if not counts or not n or not obs.get("window_s"):
        return None
    _, _, hit, ticks = counts
    need = flops_sarvam.decode_bytes(
        ctx.config, ticks, n, obs["sum_pos_in_window"], hit,
        param_bytes=_WIDTH[ctx.config["param_dtype"]],
        cache_bytes=_WIDTH[ctx.config["cache_dtype"]])
    return 100.0 * need / obs["window_s"] / ctx.peaks["hbm_bytes_per_s"]


def moe_tokens_per_expert(obs, ctx):
    """Real rows' assignments to experts held here, per held expert, expert
    layer and tick: what each expert sees of a tick."""
    counts = _counts(obs)
    if not counts:
        return None
    _, held, _, ticks = counts
    w = flops_sarvam.weight_counts(ctx.config)
    return held / (w["held"] * w["expert_layers"] * ticks)
