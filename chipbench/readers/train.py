"""Readers of the training cells."""

from __future__ import annotations

from chipbench import flops


def tokens_per_s(obs, ctx):
    """Positions trained over the whole window (closed by
    ``block_until_ready`` on the last state)."""
    if not obs.get("tokens") or not obs.get("window_s"):
        return None
    return obs["tokens"] / obs["window_s"]


def batch_wait_ms(obs, ctx):
    """Mean time the loop waited in ``next(stream)``, per step."""
    if not obs.get("steps"):
        return None
    return 1e3 * obs["batch_wait_s"] / obs["steps"]


def step_mfu_pct(obs, ctx):
    """Tokens/s times the required FLOPs of a token (causal attention
    counted once, forward times three) over the chip's bf16 peak."""
    rate = tokens_per_s(obs, ctx)
    if rate is None:
        return None
    need = flops.train_flops_per_token(ctx.config["model"],
                                       ctx.config["seq_len"])
    return 100.0 * rate * need / ctx.peaks["bf16_flops_per_s"]
