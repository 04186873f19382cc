"""Readers of the hybrid (state-space, window, one shared full K/V) serve
cell: the whole decode step's shares of the chip's peaks, and how much of
the full-length K/V that a step reads is live.  They read the program's
counters ``serve_ctx_positions``, ``serve_rows_stepped`` and
``serve_window_positions`` among the window's ``events``; a program that
does not count them reads None and the metric is left out.
"""

from __future__ import annotations

from chipbench import flops_phi4flash

_WIDTH = {"float32": 4, "bfloat16": 2}


def _counts(obs):
    events = obs.get("events") or {}
    got = [events.get(name) for name in (
        "serve_batches", "serve_rows_stepped", "serve_ctx_positions",
        "serve_window_positions")]
    if not all(got) or not obs.get("window_s"):
        return None
    return got


def hybrid_decode_hbm_pct(obs, ctx):
    """Bytes the window's ticks had to move (every weight once a tick, the
    stepped rows' live K/V positions once a reader or ring, their
    recurrent state) over the window and the chip's HBM peak."""
    counts = _counts(obs)
    if not counts:
        return None
    need = flops_phi4flash.decode_bytes(
        ctx.config, *counts, param_bytes=_WIDTH[ctx.config["param_dtype"]],
        cache_bytes=_WIDTH[ctx.config["cache_dtype"]])
    return 100.0 * need / obs["window_s"] / ctx.peaks["hbm_bytes_per_s"]


def hybrid_decode_mfu_pct(obs, ctx):
    """Required FLOPs of the steps the window's ticks made over the window
    and the chip's bf16 peak."""
    counts = _counts(obs)
    if not counts:
        return None
    need = flops_phi4flash.decode_flops(ctx.config, *counts[1:])
    return 100.0 * need / obs["window_s"] / ctx.peaks["bf16_flops_per_s"]


def cache_live_pct(obs, ctx):
    """Live positions of the stepped rows over ``length`` a row stepped:
    the share of the masked full-buffer read that is wanted."""
    counts = _counts(obs)
    if not counts:
        return None
    _, rows, ctx_positions, _ = counts
    return 100.0 * ctx_positions / (ctx.config["length"] * rows)
