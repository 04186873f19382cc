"""Reader of how often ``PolicyServer`` had a tick in flight when it launched
the next one: the counter ``serve_ticks_overlapped`` (ticks dispatched while an
older tick's reply was still to be fetched) over ``serve_batches``, as the
driver forwards them among the window's ``events``.  A program that does not
count it, or a window without a tick, reads None.
"""

from __future__ import annotations


def tick_overlap_pct(obs, ctx):
    """Share of the window's ticks that were launched behind a tick still in
    flight: the server's admission and replies then ran beside the device."""
    events = obs.get("events") or {}
    overlapped = events.get("serve_ticks_overlapped")
    ticks = events.get("serve_batches")
    if overlapped is None or not ticks:
        return None
    return 100.0 * overlapped / ticks
