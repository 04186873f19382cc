"""Readers of the serving cells."""

from __future__ import annotations

import numpy as np

from chipbench import flops


def tokens_per_s(obs, ctx):
    """``step`` replies received by all clients over the whole window."""
    if not obs.get("replies_in_window") or not obs.get("window_s"):
        return None
    return obs["replies_in_window"] / obs["window_s"]


def step_p95_ms(obs, ctx):
    """95th percentile of every ``step`` RPC's client-side latency."""
    if not len(obs.get("step_s", ())):
        return None
    return 1e3 * float(np.percentile(obs["step_s"], 95))


def first_pred_p90_ms(obs, ctx):
    """90th percentile of ``reset(prefix=)`` latency: admission + prefill."""
    if not len(obs.get("reset_s", ())):
        return None
    return 1e3 * float(np.percentile(obs["reset_s"], 90))


def _stage_mean_ms(obs, name):
    stage = (obs.get("stages") or {}).get(name)
    if not stage or not stage["count"]:
        return None
    return 1e3 * stage["total_s"] / stage["count"]


def queue_wait_ms(obs, ctx):
    """Server ``StageTimer`` ``queue_wait``, mean per request."""
    return _stage_mean_ms(obs, "queue_wait")


def compute_ms(obs, ctx):
    """Server ``StageTimer`` ``compute``, mean per tick: the host's time
    inside the model call, a tick's dispatch plus the fetch of its reply
    (``np.asarray``), added once where the tick is retired.  Since one tick
    is kept in flight, what ran between the two is not in it, so this is
    not the device's time for a tick."""
    return _stage_mean_ms(obs, "compute")


def batch_rows_mean(obs, ctx):
    """Real rows per batch: the ``step`` calls answered (each was one real
    row of one tick; padding rows answer nobody) over ``serve_batches``."""
    batches = (obs.get("events") or {}).get("serve_batches", 0)
    if not batches or not len(obs.get("step_s", ())):
        return None
    return len(obs["step_s"]) / batches


def batch_pad_pct(obs, ctx):
    """Pad rows over rows computed in the window: a tick of ``n`` real rows
    runs the least bucket that holds them, and ``serve_batch_pad`` counts
    the ``bucket - n`` rows that answer nobody (a server that padded
    nothing counted nothing: that reads 0, which is a reading)."""
    events = obs.get("events") or {}
    real = len(obs.get("step_s", ()))
    if not events.get("serve_batches") or not real:
        return None
    pad = events.get("serve_batch_pad", 0)
    return 100.0 * pad / (real + pad)


def decode_mfu_pct(obs, ctx):
    """Required FLOPs of the steps answered in the window over the window
    and the chip's bf16 peak."""
    n = obs.get("replies_in_window")
    if not n or not obs.get("window_s"):
        return None
    need = flops.decode_flops(ctx.config["model"], n,
                              obs["sum_pos_in_window"])
    return 100.0 * need / obs["window_s"] / ctx.peaks["bf16_flops_per_s"]


def decode_hbm_pct(obs, ctx):
    """Required bytes of the ticks run (parameters once a tick, each stepped
    row's live K/V positions) over the window and the chip's HBM peak."""
    n = obs.get("replies_in_window")
    ticks = (obs.get("events") or {}).get("serve_batches", 0)
    if not n or not ticks or not obs.get("window_s"):
        return None
    width = {"float32": 4, "bfloat16": 2}
    need = flops.decode_bytes(
        ctx.config["model"], ticks, obs["sum_pos_in_window"],
        param_bytes=width[ctx.config["param_dtype"]],
        cache_bytes=width[ctx.config["cache_dtype"]])
    return 100.0 * need / obs["window_s"] / ctx.peaks["hbm_bytes_per_s"]
