"""Readers that every cell shares.  A reader takes what the driver observed
(``obs``) and the run's context (``ctx``: configuration, workload file, peaks)
and returns one number, or None where it finds nothing to read."""

from __future__ import annotations


def setup_s(obs, ctx):
    """Process start to the window's first step or request."""
    return obs.get("setup_s")


def device_idle_pct(obs, ctx):
    """1 - (union of device-operation intervals / traced window)."""
    traced = obs.get("trace")
    if not traced or not traced["window_s"] or not traced["busy_s"]:
        return None
    return 100.0 * (1.0 - traced["busy_s"] / traced["window_s"])
