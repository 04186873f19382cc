"""Readers of the sliding-window, softmax-routed serve cell: the whole
decode step's shares of the chip's peaks, and how much of what a tick has
to move is live K/V.  They read the program's counters
``serve_moe_assignments``, ``serve_moe_assignments_held``,
``serve_moe_experts_hit``, ``serve_rows_stepped``,
``serve_window_positions`` and ``serve_ctx_positions`` (with
``serve_batches``) among the window's ``events``; a program that does not
count them reads None and the metric is left out.
"""

from __future__ import annotations

from chipbench import flops_mellum2

_WIDTH = {"float32": 4, "bfloat16": 2}
_NAMES = ("serve_batches", "serve_rows_stepped", "serve_moe_assignments",
          "serve_moe_assignments_held", "serve_moe_experts_hit",
          "serve_window_positions", "serve_ctx_positions")


def _counts(obs):
    """The window's counts under ``_NAMES``, or None."""
    events = obs.get("events") or {}
    got = dict(zip(_NAMES, (events.get(name) for name in _NAMES)))
    if not all(got.values()) or not obs.get("window_s"):
        return None
    return got


def _need_bytes(c, ctx):
    return flops_mellum2.decode_bytes(
        ctx.config, c["serve_batches"], c["serve_rows_stepped"],
        c["serve_moe_experts_hit"], c["serve_window_positions"],
        c["serve_ctx_positions"],
        param_bytes=_WIDTH[ctx.config["param_dtype"]],
        cache_bytes=_WIDTH[ctx.config["cache_dtype"]])


def moe_swa_decode_hbm_pct(obs, ctx):
    """Bytes the window's ticks had to move (every weight outside the
    experts once a tick, the held experts that got a token, one embedding
    row a step, the live K/V of every window ring and full layer) over the
    window and the chip's HBM peak."""
    c = _counts(obs)
    if not c:
        return None
    return (100.0 * _need_bytes(c, ctx) / obs["window_s"]
            / ctx.peaks["hbm_bytes_per_s"])


def moe_swa_decode_mfu_pct(obs, ctx):
    """Required FLOPs of the steps the window's ticks made over the window
    and the chip's bf16 peak."""
    c = _counts(obs)
    if not c:
        return None
    per_token = (ctx.config["num_experts_per_tok"]
                 * c["serve_moe_assignments_held"]
                 / c["serve_moe_assignments"])
    need = flops_mellum2.decode_flops(
        ctx.config, c["serve_rows_stepped"], per_token,
        c["serve_window_positions"], c["serve_ctx_positions"])
    return 100.0 * need / obs["window_s"] / ctx.peaks["bf16_flops_per_s"]


def swa_kv_bytes_pct(obs, ctx):
    """The live K/V's share of the bytes a tick has to move."""
    c = _counts(obs)
    if not c:
        return None
    kv = flops_mellum2.kv_bytes(
        ctx.config, c["serve_window_positions"], c["serve_ctx_positions"],
        _WIDTH[ctx.config["cache_dtype"]])
    return 100.0 * kv / _need_bytes(c, ctx)
