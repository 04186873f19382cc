"""Readers of ``PolicyServer``'s phase clock (PR 40): the serve loop's wall
time cut into eleven exclusive phases, each a running counter in
microseconds (``serve_idle_us`` ... ``serve_loop_us``), the thread's time with
nothing launched (``serve_drained_us``, and ``serve_drained_wait_us`` of it
waiting on the clients), and the requests' stamps: the time on the wire
(``serve_wire_in_us`` over ``serve_wire_in_n``) and the clients' turnaround
(``serve_client_turn_us`` over ``serve_client_turn_n``), as the driver
forwards them among the window's ``events``.

Every reader first checks that the phases tile the window: their sum within
[95%, 101%] of ``window_s``.  A program without the clock (the parent: the
counters are absent) or a broken clock reads None, never a wrong number; so
does a zero divisor.
"""

from __future__ import annotations

#: the eleven exclusive phases, which together are the thread's wall time
PHASES = ("serve_idle_us", "serve_poll_us", "serve_slice_us",
          "serve_admit_us", "serve_prefill_dispatch_us", "serve_assemble_us",
          "serve_dispatch_us", "serve_fetch_wait_us", "serve_reply_us",
          "serve_weights_us", "serve_loop_us")
#: the phases in which the thread works, not waits (on the clients, the
#: wire or the device)
WORK = ("serve_admit_us", "serve_prefill_dispatch_us", "serve_assemble_us",
        "serve_dispatch_us", "serve_reply_us", "serve_weights_us",
        "serve_loop_us")
TILE = (0.95, 1.01)


def _tiled(obs):
    """The window's events, where the phase clock tiles the window."""
    events = obs.get("events") or {}
    window_s = obs.get("window_s")
    if "serve_loop_us" not in events or not window_s:
        return None
    covered = sum(events.get(name, 0) for name in PHASES) / 1e6 / window_s
    if not TILE[0] <= covered <= TILE[1]:
        return None
    return events


def _ratio(obs, nums, den, scale):
    """``scale`` x the sum of the counters ``nums`` over the counter
    ``den``, in a tiled window."""
    events = _tiled(obs)
    if events is None or not events.get(den):
        return None
    return scale * sum(events.get(n, 0) for n in nums) / events[den]


def host_ms(obs, ctx):
    """The thread's working time a tick: admission, the prefills' and the
    ticks' dispatches, assembly, replies, weights and the loop's own code."""
    return _ratio(obs, WORK, "serve_batches", 1e-3)


def admit_ms(obs, ctx):
    """Draining both wires and admitting (the shm pump, decoding, control
    commands; a prefill's dispatch left out), a tick."""
    return _ratio(obs, ("serve_admit_us",), "serve_batches", 1e-3)


def dispatch_ms(obs, ctx):
    """A tick's dispatch on the host (``step_rows`` until it returns)."""
    return _ratio(obs, ("serve_dispatch_us",), "serve_batches", 1e-3)


def drained_pct(obs, ctx):
    """Share of the window in which nothing was launched and unfetched: the
    device's queue was certainly empty (a lower bound of its idle share)."""
    events = _tiled(obs)
    if events is None:
        return None
    return 100.0 * events.get("serve_drained_us", 0) / 1e6 / obs["window_s"]


def drained_wait_pct(obs, ctx):
    """Share of that drained time spent waiting on the clients (idle or
    blocked on the wire), the rest being the server's own work."""
    return _ratio(obs, ("serve_drained_wait_us",), "serve_drained_us", 100.0)


def wire_in_ms(obs, ctx):
    """A request's time from its client's send to its admission."""
    return _ratio(obs, ("serve_wire_in_us",), "serve_wire_in_n", 1e-3)


def client_turn_ms(obs, ctx):
    """A client's turnaround: the previous reply's send to its next
    request's send (the reply's wire time and the client's own code)."""
    return _ratio(obs, ("serve_client_turn_us",), "serve_client_turn_n", 1e-3)
