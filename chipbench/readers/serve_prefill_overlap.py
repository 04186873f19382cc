"""Reader of how often a ``reset``'s prefill shared the device's queue: the
counter ``serve_prefills_overlapped`` (prefills dispatched while something
launched was still unfetched, or fetched after something had been dispatched
behind them) over ``serve_prefills``, as the driver forwards them among the
window's ``events``.  A program that does not count it (one that fences a
prefill where it is made), or a window without a prefill, reads None.
"""

from __future__ import annotations


def prefill_overlap_pct(obs, ctx):
    """Share of the window's prefills that ran beside other launched work:
    the server's thread did not have to drain the device's queue for them."""
    events = obs.get("events") or {}
    overlapped = events.get("serve_prefills_overlapped")
    prefills = events.get("serve_prefills")
    if overlapped is None or not prefills:
        return None
    return 100.0 * overlapped / prefills
