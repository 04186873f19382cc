"""Readers of the phases of ``PolicyServer``'s one thread that no stage
times: the two running counters ``serve_prefill_us`` (inside
``model.prefill_rows``) and ``serve_idle_us`` (polling with nothing queued),
as the driver forwards them among the window's ``events``.  A program that
does not count them, or a zero divisor, reads None.
"""

from __future__ import annotations


def _event(obs, name):
    return (obs.get("events") or {}).get(name)


def prefill_ms(obs, ctx):
    """Mean time of one prefill: admission's ``model.prefill_rows``, fenced."""
    total, n = _event(obs, "serve_prefill_us"), _event(obs, "serve_prefills")
    if total is None or not n:
        return None
    return total / n / 1e3


def prefill_block_pct(obs, ctx):
    """Share of the window in which the server's thread was inside a
    prefill, so that no tick could start."""
    total = _event(obs, "serve_prefill_us")
    if total is None or not obs.get("window_s"):
        return None
    return 100.0 * total / 1e6 / obs["window_s"]


def idle_wait_ms(obs, ctx):
    """Per tick, how long the server had nothing queued (the clients'
    turnaround): per tick, so that a faster tick does not read as a worse
    number."""
    total, ticks = _event(obs, "serve_idle_us"), _event(obs, "serve_batches")
    if total is None or not ticks:
        return None
    return total / ticks / 1e3
