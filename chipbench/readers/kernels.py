"""Readers of single kernels: a kernel's device seconds in the traced
extent, found by the name the program gives its ``pallas_call``, against the
operations the mathematics needs there and the chip's peak.

A kernel's name reaches ``breakdown.device_ops`` as the custom call's
instruction name (``flash_fwd[tpu_custom_call]``; the autodiff wrapper may
decorate it), so rows are matched by substring.  ``device_ops`` holds the
ten longest rows only: a kernel that is not among them, or a program that
does not name its kernels, reads None and the metric is left out.
"""

from __future__ import annotations

FLASH_FWD = "flash_fwd"  # ops/flash_attention.py, the forward kernel
FLASH_BWD = "flash_bwd"  # the two backward kernels: flash_bwd_dq, flash_bwd_dkv


def attention_forward_flops_per_token(model, seq_len):
    """Causal attention's own products, forward, per position: scores and
    apply are ``T*d_model`` each (the mean over a causal sequence, the
    masked half not counted), per layer.  The second term of
    ``flops.forward_flops_per_token``."""
    return model["n_layers"] * 2.0 * seq_len * model["d_model"]


def kernel_seconds(obs, needle):
    """Device seconds of the ``device_ops`` rows whose name holds
    ``needle``, or None where there is no trace or no such row."""
    traced = obs.get("trace")
    if not traced:
        return None
    rows = [s for name, s in traced.get("device_ops") or () if needle in name]
    return sum(rows) if rows else None


def _attention_mfu_pct(obs, ctx, needle, passes):
    seconds = kernel_seconds(obs, needle)
    if not seconds or not obs.get("tokens") or not obs.get("window_s"):
        return None
    traced_s = obs["trace"].get("window_s")
    if not traced_s:
        return None
    # the steps of the traced extent, at the whole window's rate
    tokens = obs["tokens"] / obs["window_s"] * traced_s
    need = passes * tokens * attention_forward_flops_per_token(
        ctx.config["model"], ctx.config["seq_len"])
    return 100.0 * need / seconds / ctx.peaks["bf16_flops_per_s"]


def flash_fwd_mfu_pct(obs, ctx):
    """Required forward attention FLOPs of the traced steps over the
    forward kernel's device seconds and the bf16 peak."""
    return _attention_mfu_pct(obs, ctx, FLASH_FWD, 1.0)


def flash_bwd_mfu_pct(obs, ctx):
    """The same for the two backward kernels together (dq and dkv): the
    backward needs twice the forward's products."""
    return _attention_mfu_pct(obs, ctx, FLASH_BWD, 2.0)
