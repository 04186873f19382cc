"""Readers of the Mamba-2 serve cell: the whole decode step's shares of the
chip's peaks, and how much of what a tick has to move is the Mamba-2
layers' recurrent state.  They read the program's counters
``serve_ctx_positions``, ``serve_rows_stepped`` and ``serve_state_bytes``
(with ``serve_batches``) among the window's ``events``; a program that does
not count them reads None and the metric is left out.
"""

from __future__ import annotations

from chipbench import flops_granite4h

_WIDTH = {"float32": 4, "bfloat16": 2}


def _counts(obs):
    """``(ticks, rows stepped, live positions, state bytes)``, or None."""
    events = obs.get("events") or {}
    got = [events.get(name) for name in (
        "serve_batches", "serve_rows_stepped", "serve_ctx_positions",
        "serve_state_bytes")]
    if not all(got) or not obs.get("window_s"):
        return None
    return got


def _need_bytes(counts, ctx):
    return flops_granite4h.decode_bytes(
        ctx.config, *counts, param_bytes=_WIDTH[ctx.config["param_dtype"]],
        cache_bytes=_WIDTH[ctx.config["cache_dtype"]])


def ssd_decode_hbm_pct(obs, ctx):
    """Bytes the window's ticks had to move (every weight of the layers
    and the tied table once a tick, one embedding row a step, the
    recurrent state by the program's counter, the stepped rows' live K/V
    positions once an attention layer) over the window and the chip's HBM
    peak."""
    counts = _counts(obs)
    if not counts:
        return None
    return (100.0 * _need_bytes(counts, ctx) / obs["window_s"]
            / ctx.peaks["hbm_bytes_per_s"])


def ssd_decode_mfu_pct(obs, ctx):
    """Required FLOPs of the steps the window's ticks made over the window
    and the chip's bf16 peak."""
    counts = _counts(obs)
    if not counts:
        return None
    need = flops_granite4h.decode_flops(ctx.config, *counts[1:3])
    return 100.0 * need / obs["window_s"] / ctx.peaks["bf16_flops_per_s"]


def ssd_state_bytes_pct(obs, ctx):
    """The recurrent state's share of the bytes a tick has to move."""
    counts = _counts(obs)
    if not counts:
        return None
    return 100.0 * counts[3] / _need_bytes(counts, ctx)
