"""The phase clock on ``PolicyServer``'s thread, the device's empty-queue
time by the program's own count, and a request's time on the wire
(docs/serving.md "The phase clock").

The serve loop's wall time is cut into the exclusive phases of
``SERVE_PHASES``, each a counter in microseconds, so that over any
stretch of the loop they add up to the thread's wall time;
``serve_drained_us`` is the part of it with nothing launched and
unfetched.  ``ServeClient`` stamps each request with its send time and
the server's send stamp on the previous reply; the server counts the
request's time on the wire and the client's turnaround from them.
Locked here on the CPU: with a ``LinearModel`` and a tiny
``SeqFormerModel`` under closed-loop clients, and with a stub whose
replies become ready when the test says so.  Every wait is bounded.
"""

import threading
import time

import numpy as np
import pytest

from blendjax import wire
from blendjax.btt.faults import FaultPolicy
from blendjax.obs.spans import now_us
from blendjax.serve import LinearModel, ServeClient, start_server_thread
from blendjax.serve.server import SERVE_PHASES
from blendjax.utils.timing import SERVE_EVENTS, EventCounters

WAIT_S = 20.0
OBS = np.asarray([1.0, 2.0], np.float32)


class _Gated:
    """A reply that is ready when the test opens its gate."""

    def __init__(self, rows):
        self.rows = rows
        self.gate = threading.Event()
        self.fetching = threading.Event()

    def is_ready(self):
        return self.gate.is_set()

    def __array__(self, dtype=None, copy=None):
        self.fetching.set()
        if not self.gate.wait(WAIT_S):
            raise TimeoutError("the test never opened this reply")
        return self.rows


class _GatedModel:
    """``pred = sum(obs)``, handed out gated: ``calls`` holds each
    step's reply in dispatch order."""

    kind = "gated"
    obs_dim = 2

    def __init__(self, slots=4):
        self.slots = self.pad_slot = slots
        self.calls = []

    def reset_rows(self, idx):
        pass

    def step_rows(self, idx, obs):
        reply = _Gated(obs.sum(-1, keepdims=True).astype(np.float32))
        self.calls.append(reply)
        return reply


def _until(cond, what, timeout=WAIT_S):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _client(address, **kwargs):
    kwargs.setdefault("fault_policy", FaultPolicy(max_retries=0))
    return ServeClient(address, timeoutms=int(WAIT_S * 1e3), **kwargs)


def _delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


def test_the_phase_counters_are_in_the_vocabulary():
    from blendjax.obs.hub import TelemetryHub

    zero_filled = TelemetryHub().scrape()["counters"]
    for name in SERVE_PHASES + (
            "serve_drained_us", "serve_drained_wait_us", "serve_wire_in_us",
            "serve_wire_in_n", "serve_client_turn_us",
            "serve_client_turn_n"):
        assert name in SERVE_EVENTS
        assert zero_filled[name] == 0
    assert len(set(SERVE_PHASES)) == 11 and SERVE_PHASES[-1] == \
        "serve_loop_us"


def _tiny_seqformer():
    import jax

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    tiny = dict(obs_dim=2, d_model=32, n_heads=2, n_layers=2, max_len=64)
    return SeqFormerModel(seqformer.init(jax.random.PRNGKey(0), **tiny),
                          slots=4, length=64)


@pytest.mark.parametrize("kind", ["linear", "seqformer"])
def test_the_phases_tile_the_threads_wall_time(kind):
    """Three closed-loop clients, a few hundred steps and resets with
    prefixes among them: between two ``stats`` calls the eleven phases
    add up to the server thread's wall time, read on that thread where
    each call reads the counters."""
    model = (LinearModel(obs_dim=2, slots=4) if kind == "linear"
             else _tiny_seqformer())
    counters = EventCounters()
    with start_server_thread(model, counters=counters,
                             max_batch=4) as h:
        # the wall time on the server's own thread, where ``stats``
        # settles the clock
        settled, walls = h.server._settled_counters, []
        h.server._settled_counters = lambda: (
            settled(), walls.append(time.perf_counter()))[0]
        probe = _client(h.address)
        clients = [_client(h.address) for _ in range(3)]
        before = probe.stats()["counters"]
        errors = []

        def run(c, seed):
            rng = np.random.default_rng(seed)
            try:
                for episode in range(3):
                    c.reset(prefix=rng.standard_normal((4, 2)))
                    for _ in range(40):
                        c.step(rng.standard_normal(2))
                    c.close_episode()
            except Exception as exc:  # noqa: BLE001 - handed to the test
                errors.append(exc)

        threads = [threading.Thread(target=run, args=(c, i))
                   for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors and not any(t.is_alive() for t in threads)
        after = probe.stats()["counters"]
        for c in clients + [probe]:
            c.close()
    wall_us = (walls[1] - walls[0]) * 1e6
    phases = {name: _delta(before, after, name) for name in SERVE_PHASES}
    assert 0.95 <= sum(phases.values()) / wall_us <= 1.01, (wall_us, phases)
    assert _delta(before, after, "serve_batches") >= 3 * 120 / 4
    for name in ("serve_admit_us", "serve_assemble_us", "serve_dispatch_us",
                 "serve_reply_us", "serve_prefill_dispatch_us",
                 "serve_loop_us"):
        assert phases[name] > 0, name
    assert phases["serve_weights_us"] == 0  # no WeightBus subscription
    drained = _delta(before, after, "serve_drained_us")
    drained_wait = _delta(before, after, "serve_drained_wait_us")
    # each counter carries its own sub-microsecond rest: a sum of floors
    # may read a microsecond or so under the floor of the sum
    slack = len(SERVE_PHASES) + 2
    assert 0 <= drained_wait <= drained + slack
    assert drained <= sum(phases.values()) + slack
    assert drained_wait <= (phases["serve_idle_us"] + phases["serve_poll_us"]
                            + slack)


def test_drained_grows_only_with_nothing_launched():
    """A tick launched and not ready: the window polls in slices and the
    device's queue is not empty, so ``serve_drained_us`` stands still
    while ``serve_poll_us`` grows; once it is answered and nothing is
    launched, the idle wait is drained time, and a wait on the clients."""
    model, counters = _GatedModel(), EventCounters()
    with start_server_thread(model, counters=counters, tick_ms=2.0) as h:
        a, b = _client(h.address), _client(h.address)
        a.reset()
        b.reset()  # a live episode that could send: the window waits
        step = {}
        t = threading.Thread(target=lambda: step.update(r=a.step(OBS)))
        t.start()
        _until(lambda: model.calls, "a's tick to be launched")
        time.sleep(0.02)  # the launch's own phases pushed
        drained0 = counters.get("serve_drained_us")
        poll0 = counters.get("serve_poll_us")
        time.sleep(0.3)
        drained1 = counters.get("serve_drained_us")
        poll1 = counters.get("serve_poll_us")
        assert drained1 - drained0 < 2000, (drained0, drained1)
        assert poll1 - poll0 > 150_000
        model.calls[0].gate.set()
        t.join(WAIT_S)
        assert step["r"]["pred"][0] == 3.0
        time.sleep(0.02)
        drained2 = counters.get("serve_drained_us")
        wait2 = counters.get("serve_drained_wait_us")
        time.sleep(0.3)
        assert counters.get("serve_drained_us") - drained2 > 150_000
        assert counters.get("serve_drained_wait_us") - wait2 > 150_000
        a.close()
        b.close()


@pytest.mark.parametrize("shm", [False, True], ids=["tcp", "shm"])
def test_wire_and_turn_stamps_ride_both_wires(shm):
    counters = EventCounters()
    with start_server_thread(LinearModel(obs_dim=2, slots=2),
                             counters=counters) as h:
        c = _client(h.address, shm="auto" if shm else False)
        c.reset()
        before = c.stats()["counters"]
        assert c.transport == ("shm" if shm else "tcp")
        for _ in range(10):
            reply = c.step(OBS)
            assert wire.SENT_US_KEY not in reply  # popped by the client
        after = c.stats()["counters"]
        c.close()
    # ten steps and the second ``stats``, each stamped with its send and
    # the reply before it
    assert _delta(before, after, "serve_wire_in_n") == 11
    assert _delta(before, after, "serve_client_turn_n") == 11
    wire_us = _delta(before, after, "serve_wire_in_us")
    turn_us = _delta(before, after, "serve_client_turn_us")
    assert 0 <= wire_us < 11 * 1e6 and 0 < turn_us < 11 * 1e6


class _Raw:
    """A DEALER socket speaking the serve wire by hand."""

    def __init__(self, address):
        import zmq

        self.sock = zmq.Context.instance().socket(zmq.DEALER)
        self.sock.setsockopt(zmq.LINGER, 0)
        self.sock.connect(address)

    def send(self, msg):
        wire.send_message_dealer(self.sock, dict(msg), raw_buffers=True)

    def recv(self):
        assert self.sock.poll(int(WAIT_S * 1e3)), "no reply"
        return wire.recv_message_dealer(self.sock)

    def close(self):
        self.sock.close(0)


def test_a_retried_request_is_counted_once():
    """A retry answered from the reply cache, and a duplicate of a step
    still in flight, count no time on the wire and no turnaround."""
    model, counters = _GatedModel(), EventCounters()
    with start_server_thread(model, counters=counters) as h:
        raw = _Raw(h.address)
        stamp = {wire.SENT_US_KEY: now_us()}
        raw.send({"cmd": "reset", wire.BTMID_KEY: "r1", **stamp})
        reset = raw.recv()
        assert isinstance(reset[wire.SENT_US_KEY], int)  # stamped reply
        raw.send({"cmd": "reset", wire.BTMID_KEY: "r1", **stamp})
        assert raw.recv()["slot"] == reset["slot"]  # from the cache
        # a second live episode, unstamped: with a tick launched the
        # window then reads the wire while it waits
        raw.send({"cmd": "reset", wire.BTMID_KEY: "r2"})
        raw.recv()
        step = {"cmd": "step", wire.BTMID_KEY: "s1", "slot": reset["slot"],
                "episode": reset["episode"], "obs": OBS,
                wire.SENT_US_KEY: now_us(),
                wire.REPLY_SENT_US_KEY: reset[wire.SENT_US_KEY]}
        raw.send(step)
        _until(lambda: model.calls, "the step to be launched")
        raw.send(step)  # a retry of the step in flight
        _until(lambda: counters.get("serve_dup_inflight") == 1, "the dup")
        model.calls[0].gate.set()
        assert raw.recv()["pred"][0] == 3.0
        raw.close()
    snap = counters.snapshot()
    assert snap["serve_cache_hits"] == 1
    assert snap["serve_wire_in_n"] == 2  # the reset, the step
    assert snap["serve_client_turn_n"] == 1  # the step


def test_a_request_without_stamps_counts_nothing():
    counters = EventCounters()
    with start_server_thread(LinearModel(obs_dim=2, slots=2),
                             counters=counters) as h:
        raw = _Raw(h.address)
        raw.send({"cmd": "reset", wire.BTMID_KEY: "r1"})
        reset = raw.recv()
        raw.send({"cmd": "step", wire.BTMID_KEY: "s1", "obs": OBS,
                  "slot": reset["slot"], "episode": reset["episode"]})
        assert "pred" in raw.recv()
        snap = counters.snapshot()
        assert snap["serve_requests"] == 2
        for name in ("serve_wire_in_us", "serve_wire_in_n",
                     "serve_client_turn_us", "serve_client_turn_n"):
            assert snap.get(name, 0) == 0, name
        # a send stamp alone counts the wire, and no turnaround
        raw.send({"cmd": "step", wire.BTMID_KEY: "s2", "obs": OBS,
                  "slot": reset["slot"], "episode": reset["episode"],
                  wire.SENT_US_KEY: now_us()})
        raw.recv()
        raw.close()
    snap = counters.snapshot()
    assert snap["serve_wire_in_n"] == 1
    assert snap.get("serve_client_turn_n", 0) == 0


def test_a_switch_costs_microseconds():
    """The clock's own cost, on this CPU: a phase entered and left (two
    switches and the span's no-op where jax is not profiling)."""
    from blendjax.serve.server import _PhaseClock

    counters = EventCounters()
    clock = _PhaseClock(counters, lambda: True)
    n = 20000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with clock("serve_admit_us", "serve.admit"):
            pass
    per_switch_ns = (time.perf_counter_ns() - t0) / (2 * n)
    clock.settle()
    assert per_switch_ns < 20_000, per_switch_ns
    snap = counters.snapshot()
    total = snap.get("serve_admit_us", 0) + snap.get("serve_loop_us", 0)
    assert total == pytest.approx(snap["serve_drained_us"], abs=2)
