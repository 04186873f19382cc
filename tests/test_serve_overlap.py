"""One serve tick in flight, and the prefills beside it (docs/serving.md
"One tick in flight", "Batched prefill admission").

``PolicyServer`` launches a tick (assemble + dispatch) and retires it
(fetch + replies) as two halves, and keeps at most one launched tick
between two turns of its loop, so admission, the next launch and the
older tick's replies run beside the device.  A ``reset``'s prefill is
launched the same way where the reset is admitted, behind whatever is in
flight, and answered where it is retired.  Locked here, on the CPU, with
a stub model whose replies become ready when the test says so: the
overlap itself and its counters, the lone client's unchanged sequence,
and the guarantees the overlap could break — exactly-once, the version
stamps, the lost pool, the leases.  Then real models: concurrent
closed-loop clients get exactly what serial decode gives, and what a
fenced twin gives when they reset with prefixes.

Every wait in this file is bounded: a test that cannot finish fails.
"""

import threading
import time

import numpy as np
import pytest

from blendjax.btt.faults import FaultPolicy
from blendjax.serve import ServeClient, start_server_thread
from blendjax.serve.server import SlotPoolLost
from blendjax.utils.timing import EventCounters

WAIT_S = 20.0


class _Gated:
    """A reply that is ready when the test opens its gate."""

    def __init__(self, rows, error=None):
        self.rows, self.error = rows, error
        self.gate = threading.Event()
        self.fetching = threading.Event()

    def is_ready(self):
        return self.gate.is_set()

    def __array__(self, dtype=None, copy=None):
        self.fetching.set()
        if not self.gate.wait(WAIT_S):
            raise TimeoutError("the test never opened this reply")
        if self.error is not None:
            raise self.error
        return self.rows


class StubModel:
    """``pred = w * sum(obs) + pos``, computed where the step is made;
    the reply is handed out gated, and so is a prefill's (``w`` times
    the sum of the prefix's last row).  ``calls`` holds the steps' (real
    rows, reply) and ``prefills`` the prefills' (slot, reply), each in
    dispatch order; ``log`` every call that touched a row, in the order
    the device would run them."""

    kind = "stub"
    obs_dim = 2

    def __init__(self, slots=4):
        self.slots = self.pad_slot = slots
        self.pos = np.zeros(slots + 1, np.int64)
        self.w = 1.0
        self.calls = []
        self.prefills = []
        self.log = []
        self.errors = {}  # call number -> what its fetch raises
        self.prefill_errors = {}  # prefill number -> what its fetch raises
        self.pool_rebuilds = 0

    def reset_rows(self, idx):
        self.pos[idx] = 0
        self.log.append(("reset", tuple(int(i) for i in idx)))

    def apply_weights(self, tree):
        self.w = float(tree["w"])

    def prefill_reply(self, idx, prefix):
        self.pos[idx] = len(prefix)
        slot = int(idx[0])
        reply = _Gated(prefix[-1:].sum(-1) * np.float32(self.w),
                       self.prefill_errors.get(len(self.prefills)))
        self.prefills.append((slot, reply))
        self.log.append(("prefill", (slot,)))
        return reply

    def prefill_rows(self, idx, prefix):
        raise AssertionError("the server fences no prefill where it is made")

    def step_rows(self, idx, obs):
        rows = (self.w * obs.sum(-1, keepdims=True)
                + self.pos[idx, None]).astype(np.float32)
        self.pos[idx] += 1
        real = tuple(int(i) for i in idx if i != self.pad_slot)
        reply = _Gated(rows, self.errors.get(len(self.calls)))
        self.calls.append((real, reply))
        self.log.append(("step", real))
        return reply


def _until(cond, what, timeout=WAIT_S):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


class _Call:
    """One blocking client call on a thread of its own."""

    def __init__(self, fn, *args, **kwargs):
        self.out = self.err = None

        def run():
            try:
                self.out = fn(*args, **kwargs)
            except Exception as exc:  # noqa: BLE001 - handed to the test
                self.err = exc

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def result(self, timeout=WAIT_S):
        self.thread.join(timeout)
        assert not self.thread.is_alive(), "the call never returned"
        if self.err is not None:
            raise self.err
        return self.out


def _client(h, **kwargs):
    kwargs.setdefault("fault_policy", FaultPolicy(max_retries=0))
    return ServeClient(h.address, timeoutms=int(WAIT_S * 1e3), **kwargs)


@pytest.fixture
def served():
    """A stub model behind a server, two live episodes ``a`` and ``b``
    (so that a tick of one leaves somebody who can still send)."""
    model, counters = StubModel(), EventCounters()
    with start_server_thread(model, counters=counters, tick_ms=2.0) as h:
        a, b = _client(h), _client(h)
        a.reset()
        b.reset()
        try:
            yield model, counters, h, a, b
        finally:
            for _, reply in model.calls + model.prefills:
                reply.gate.set()
            a.close()
            b.close()


OBS = np.asarray([1.0, 2.0], np.float32)
PREFIX = np.asarray([[9.0, 9.0], [0.5, 0.25], [1.0, 3.0]], np.float32)


def test_second_tick_is_launched_before_the_first_is_fetched(served):
    model, counters, h, a, b = served
    first = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick to be launched")
    second = _Call(b.step, OBS)
    # b's step is dispatched behind a's tick, whose reply nobody could
    # have fetched: its gate is shut
    _until(lambda: len(model.calls) == 2, "b's tick behind a's")
    assert not model.calls[0][1].gate.is_set()
    assert first.thread.is_alive() and second.thread.is_alive()
    assert counters.get("serve_batches") == 0
    # ... and then the server waits in the OLDER tick's fetch
    assert model.calls[0][1].fetching.wait(WAIT_S)
    assert not model.calls[1][1].fetching.is_set()
    model.calls[0][1].gate.set()
    assert first.result()["pred"][0] == 3.0
    model.calls[1][1].gate.set()
    assert second.result()["pred"][0] == 3.0
    snap = counters.snapshot()
    assert snap["serve_ticks_overlapped"] == 1
    assert snap["serve_batches"] == 2
    assert snap["serve_fetch_wait_us"] > 0  # the ticks' waits (no prefill)
    assert [rows for rows, _ in model.calls] == [(a.slot,), (b.slot,)]


def test_a_ready_tick_is_retired_before_a_follower_is_launched(served):
    model, counters, h, a, b = served
    first = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick to be launched")
    model.calls[0][1].gate.set()  # ready before anything else is queued
    assert first.result()["pred"][0] == 3.0
    second = _Call(b.step, OBS)
    _until(lambda: len(model.calls) == 2, "b's tick")
    model.calls[1][1].gate.set()
    assert second.result()["pred"][0] == 3.0
    assert counters.get("serve_ticks_overlapped") == 0


def test_a_lone_clients_tick_is_retired_at_once():
    """Nobody else can send: the server goes straight to the fetch, it
    waits neither on ``tick_ms`` nor on the socket for the reply to
    become ready."""
    model, counters = StubModel(), EventCounters()
    with start_server_thread(model, counters=counters,
                             tick_ms=5000.0) as h:
        c = _client(h)
        c.reset()
        t0 = time.monotonic()
        call = _Call(c.step, OBS)
        _until(lambda: len(model.calls) == 1, "the tick")
        reply = model.calls[0][1]
        assert reply.fetching.wait(2.0), "the server did not go to fetch"
        assert not reply.gate.is_set()
        reply.gate.set()
        out = call.result()
        assert time.monotonic() - t0 < 2.5  # far inside tick_ms
        assert out["pred"][0] == 3.0 and out["pos"] == 0
        snap = counters.snapshot()
        assert snap["serve_ticks_overlapped"] == 0
        assert snap["serve_batches"] == 1
        c.close()


def test_a_retry_of_a_step_in_flight_runs_nothing_twice(served):
    model, counters, h, a, b = served
    # a's first attempt times out while its step is in flight; the retry
    # carries the same correlation id
    retrying = ServeClient(
        h.address, timeoutms=150,
        fault_policy=FaultPolicy(max_retries=30, backoff_base=0.01,
                                 backoff_max=0.02, circuit_threshold=0,
                                 seed=1))
    retrying.slot, retrying.episode = a.slot, a.episode
    call = _Call(retrying.step, OBS)
    _until(lambda: counters.get("serve_dup_inflight") >= 1,
           "a retry to meet its step in flight")
    assert len(model.calls) == 1
    assert counters.get("serve_batches") == 0
    model.calls[0][1].gate.set()
    out = call.result()
    assert out["pred"][0] == 3.0 and out["pos"] == 0
    # a late duplicate is answered from the reply cache: nothing re-ran,
    # and the slot advanced once
    time.sleep(0.2)
    assert [rows for rows, _ in model.calls] == [(a.slot,)]
    assert model.pos[a.slot] == 1
    nxt = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 2, "a's next tick")
    model.calls[1][1].gate.set()
    assert nxt.result()["pos"] == 1
    snap = counters.snapshot()
    assert snap["serve_batches"] == 2
    assert snap.get("serve_errors", 0) == 0
    retrying.close()


class _StagedWhileFlying:
    """A WeightBus subscription that yields version 1 at once and
    version 2 the first time it is polled with a tick launched."""

    model = None

    def __init__(self, server):
        self.server = server
        self.pending = [1]
        self.staged_with_flying = False

    def _snap(self, version):
        import types

        return types.SimpleNamespace(
            model=None, version=version, step=version,
            tree=lambda: {"w": float(10 ** version)})

    def poll(self):
        if self.pending:
            return self._snap(self.pending.pop())
        if not self.staged_with_flying and self.server._launched:
            self.staged_with_flying = True
            return self._snap(2)
        return None

    def close(self):
        pass


def test_a_snapshot_staged_in_flight_is_adopted_after_the_tick():
    model, counters = StubModel(), EventCounters()
    with start_server_thread(model, counters=counters, tick_ms=2.0) as h:
        a, b = _client(h), _client(h)
        a.reset()
        b.reset()
        bus = h.server.subscriber = _StagedWhileFlying(h.server)
        _until(lambda: h.server.weight_version == 1, "version 1")
        call = _Call(a.step, OBS)
        _until(lambda: len(model.calls) == 1, "a's tick")
        # the snapshot is staged with the tick in flight; it is not
        # adopted until that tick has been answered
        _until(lambda: bus.staged_with_flying, "version 2 to be staged")
        time.sleep(0.05)
        assert h.server.weight_version == 1 and model.w == 10.0
        model.calls[0][1].gate.set()
        out = call.result()
        assert out["weight_version"] == 1
        assert out["pred"][0] == 30.0  # executed under version 1
        _until(lambda: h.server.weight_version == 2, "version 2")
        nxt = _Call(a.step, OBS)
        _until(lambda: len(model.calls) == 2, "a's next tick")
        model.calls[1][1].gate.set()
        out = nxt.result()
        assert out["weight_version"] == 2 and out["pred"][0] == 301.0
        assert counters.get("weight_adopted") == 2
        a.close()
        b.close()


def test_a_fetch_that_fails_behind_a_second_launch_fails_both_once(served):
    model, counters, h, a, b = served
    # as SeqFormerModel does it: the first reply off a lost pool raises
    # SlotPoolLost (over a rebuilt pool), a later one re-raises plainly
    model.errors = {0: SlotPoolLost("device fault (injected)"),
                    1: RuntimeError("device fault (injected)")}
    first = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    second = _Call(b.step, OBS)
    _until(lambda: len(model.calls) == 2, "b's tick behind a's")
    for _, reply in model.calls:
        reply.gate.set()
    for call in (first, second):
        with pytest.raises(RuntimeError, match="batched step failed"):
            call.result()
    # the second tick's reply was never fetched: it went with the pool
    assert not model.calls[1][1].fetching.is_set()
    snap = counters.snapshot()
    assert snap["serve_pool_rebuilds"] == 1
    assert snap["serve_errors"] == 2
    assert snap.get("serve_batches", 0) == 0
    stats = a.stats()
    assert stats["live_slots"] == 0 and stats["free_slots"] == model.slots
    with pytest.raises(RuntimeError, match="unknown episode slot"):
        a.step(OBS)
    # and it serves again after reset()
    a.reset()
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 3, "a's tick on the new pool")
    model.calls[2][1].gate.set()
    assert call.result()["pos"] == 0
    assert counters.get("serve_pool_rebuilds") == 1


def test_a_slot_reused_in_flight_answers_the_old_step_and_no_stale_one(
        served):
    model, counters, h, a, b = served
    slot, episode = a.slot, a.episode
    bystander = _client(h)  # so that somebody can still send afterwards
    bystander.reset()
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    # with a's step in flight its episode is closed and the slot handed
    # to a new tenant (what an eviction or a supervisor's close does)
    janitor, tenant = _client(h), _client(h)
    assert janitor.rpc("close", {"slot": slot, "episode": episode})["closed"]
    assert tenant.reset() == slot and tenant.episode != episode
    # the rewind is ordered behind the step that was in flight
    assert model.log[-2:] == [("step", (slot,)), ("reset", (slot,))]
    model.calls[0][1].gate.set()
    out = call.result()
    assert out["pred"][0] == 3.0 and out["pos"] == 0  # the old step's answer
    with pytest.raises(RuntimeError, match="stale episode lease"):
        a.step(OBS)  # a's lease is gone: it cannot step the new tenant
    nxt = _Call(tenant.step, OBS)
    _until(lambda: len(model.calls) == 2, "the tenant's tick")
    model.calls[1][1].gate.set()
    assert nxt.result()["pos"] == 0
    assert [rows for rows, _ in model.calls] == [(slot,), (slot,)]
    for c in (janitor, tenant, bystander):
        c.close()


# -- a reset's prefill is launched like a tick --------------------------------


def _quiet(reply, for_s=0.1):
    """Nobody went to this reply's fetch for a while."""
    return not reply.fetching.wait(for_s)


def test_a_prefill_behind_a_tick_neither_fetches_nor_delays_it(
        served):
    model, counters, h, a, b = served
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    tick = model.calls[0][1]
    newcomer = _client(h)
    admitted = _Call(newcomer.reset, PREFIX)
    # the prefill is dispatched with a's tick in flight: nobody fetched
    # that tick for it, and the device runs step, rewind, prefill
    _until(lambda: len(model.prefills) == 1, "the prefill's dispatch")
    slot, prefill = model.prefills[0]
    assert not tick.gate.is_set() and _quiet(tick)
    assert model.log[-3:] == [("step", (a.slot,)), ("reset", (slot,)),
                              ("prefill", (slot,))]
    assert counters.get("serve_batches") == 0
    # the tick's answer does not wait for the prefill behind it
    tick.gate.set()
    assert call.result()["pos"] == 0
    assert not prefill.gate.is_set() and admitted.thread.is_alive()
    assert counters.get("serve_resets") == 2  # a's and b's: not this one yet
    prefill.gate.set()
    reply = admitted.result()
    assert reply["slot"] == slot and reply["pos"] == 3
    assert reply["pred"] == 4.0
    # no step of the episode could have run before its prefill: the
    # client learnt the slot from that reply
    nxt = _Call(newcomer.step, OBS)
    _until(lambda: len(model.calls) == 2, "the newcomer's tick")
    model.calls[1][1].gate.set()
    assert nxt.result()["pos"] == 3
    snap = counters.snapshot()
    assert snap["serve_prefills"] == 1 and snap["serve_resets"] == 3
    assert snap["serve_prefills_overlapped"] == 1
    assert snap["serve_prefill_us"] > 0
    newcomer.close()


def test_a_tick_is_dispatched_behind_an_unfetched_prefill(
        served):
    model, counters, h, a, b = served
    newcomer = _client(h)
    admitted = _Call(newcomer.reset, PREFIX)
    _until(lambda: len(model.prefills) == 1, "the prefill's dispatch")
    prefill = model.prefills[0][1]
    # a and b are everybody who can send: their tick is launched behind
    # the prefill, whose reply nobody could have fetched
    first, second = _Call(a.step, OBS), _Call(b.step, OBS)
    _until(lambda: sum(len(rows) for rows, _ in model.calls) == 2,
           "a's and b's steps behind the prefill")
    assert not prefill.gate.is_set() and admitted.thread.is_alive()
    # ... and then the server waits in the OLDEST entry's fetch: the
    # prefill's, though the tick behind it is ready first
    assert prefill.fetching.wait(WAIT_S)
    for _, reply in model.calls:
        reply.gate.set()
    assert _quiet(model.calls[0][1])
    assert first.thread.is_alive() and second.thread.is_alive()
    assert counters.get("serve_batches") == 0
    prefill.gate.set()
    assert admitted.result()["pos"] == 3
    assert first.result()["pos"] == 0 and second.result()["pos"] == 0
    snap = counters.snapshot()
    # overlapped: a tick was dispatched behind it before its fetch; and
    # a tick behind a PREFILL is not a tick behind a tick
    assert snap["serve_prefills_overlapped"] == 1
    assert snap["serve_ticks_overlapped"] == 0
    newcomer.close()


def test_a_lone_prefill_is_retired_at_once_and_overlaps_nothing():
    model, counters = StubModel(), EventCounters()
    with start_server_thread(model, counters=counters,
                             tick_ms=5000.0) as h:
        c = _client(h)
        t0 = time.monotonic()
        admitted = _Call(c.reset, PREFIX)
        _until(lambda: len(model.prefills) == 1, "the prefill")
        prefill = model.prefills[0][1]
        assert prefill.fetching.wait(2.0), "the server did not go to fetch"
        time.sleep(0.02)
        prefill.gate.set()
        assert admitted.result()["pos"] == 3
        assert time.monotonic() - t0 < 2.5  # far inside tick_ms
        snap = counters.snapshot()
        assert snap["serve_prefills"] == 1
        assert snap.get("serve_prefills_overlapped", 0) == 0
        # the wait at a prefill's fetch is in ``serve_fetch_wait_us``
        # (since PR 40; no tick ran) as well as in ``serve_prefill_us``
        assert snap.get("serve_batches", 0) == 0
        assert 15_000 <= snap["serve_fetch_wait_us"] \
            <= snap["serve_prefill_us"]
        c.close()


def test_a_retry_of_a_reset_in_flight_runs_one_prefill_in_one_slot(served):
    model, counters, h, a, b = served
    retrying = ServeClient(
        h.address, timeoutms=150,
        fault_policy=FaultPolicy(max_retries=30, backoff_base=0.01,
                                 backoff_max=0.02, circuit_threshold=0,
                                 seed=1))
    admitted = _Call(retrying.reset, PREFIX)
    _until(lambda: counters.get("serve_dup_inflight") >= 1,
           "a retry to meet its prefill in flight")
    assert len(model.prefills) == 1
    stats = a.stats()
    assert stats["live_slots"] == 3 and stats["free_slots"] == 1
    model.prefills[0][1].gate.set()
    reply = admitted.result()
    assert reply["pos"] == 3 and reply["slot"] == model.prefills[0][0]
    # a late duplicate is answered from the reply cache: nothing re-ran
    time.sleep(0.2)
    assert len(model.prefills) == 1
    assert [e for e in model.log if e[0] == "prefill"] == [
        ("prefill", (reply["slot"],))]
    stats = a.stats()
    assert stats["live_slots"] == 3 and stats["free_slots"] == 1
    snap = counters.snapshot()
    assert snap["serve_prefills"] == 1 and snap["serve_resets"] == 3
    assert snap.get("serve_errors", 0) == 0
    retrying.close()


def test_a_prefill_whose_fetch_fails_frees_its_slot_and_answers_the_error(
        served):
    model, counters, h, a, b = served
    model.prefill_errors = {0: RuntimeError("device fault (injected)")}
    newcomer = _client(h)
    admitted = _Call(newcomer.reset, PREFIX)
    _until(lambda: len(model.prefills) == 1, "the prefill's dispatch")
    assert a.stats()["live_slots"] == 3
    model.prefills[0][1].gate.set()
    with pytest.raises(RuntimeError, match="prefill failed.*device fault"):
        admitted.result()
    stats = a.stats()
    assert stats["live_slots"] == 2 and stats["free_slots"] == 2
    snap = counters.snapshot()
    assert snap["serve_errors"] == 1
    assert snap.get("serve_prefills", 0) == 0 and snap["serve_resets"] == 2
    assert snap.get("serve_pool_rebuilds", 0) == 0
    # the others' episodes stand, and the slot is given out again
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    model.calls[0][1].gate.set()
    assert call.result()["pos"] == 0
    again = _Call(newcomer.reset, PREFIX)
    _until(lambda: len(model.prefills) == 2, "the second prefill")
    model.prefills[1][1].gate.set()
    assert again.result()["slot"] == model.prefills[0][0]
    newcomer.close()


@pytest.mark.parametrize("lost", ["prefill", "tick"])
def test_a_pool_lost_at_a_fetch_fails_what_was_launched_behind_it_once(
        served, lost):
    """The first reply off a lost pool raises SlotPoolLost, a later one
    re-raises plainly (as SeqFormerModel does it): whichever of a tick
    and a prefill is the older takes the other with it, once."""
    model, counters, h, a, b = served
    newcomer = _client(h)
    first, second = (SlotPoolLost("device fault (injected)"),
                     RuntimeError("device fault (injected)"))
    if lost == "prefill":
        model.prefill_errors, model.errors = {0: first}, {0: second}
        admitted = _Call(newcomer.reset, PREFIX)
        _until(lambda: len(model.prefills) == 1, "the prefill")
        calls = [_Call(a.step, OBS), _Call(b.step, OBS)]
        _until(lambda: sum(len(rows) for rows, _ in model.calls) == 2,
               "the tick behind the prefill")
    else:
        model.errors, model.prefill_errors = {0: first}, {0: second}
        calls = [_Call(a.step, OBS)]
        _until(lambda: len(model.calls) == 1, "a's tick")
        admitted = _Call(newcomer.reset, PREFIX)
        _until(lambda: len(model.prefills) == 1, "the prefill behind it")
    older, younger = ((model.prefills[0][1], model.calls[0][1])
                      if lost == "prefill" else
                      (model.calls[0][1], model.prefills[0][1]))
    for _, reply in model.calls + model.prefills:
        reply.gate.set()
    with pytest.raises(RuntimeError, match="prefill failed"):
        admitted.result()
    for call in calls:
        with pytest.raises(RuntimeError, match="batched step failed"):
            call.result()
    # the younger entry's reply was never fetched: it went with the pool
    assert older.fetching.is_set() and not younger.fetching.is_set()
    snap = counters.snapshot()
    assert snap["serve_pool_rebuilds"] == 1
    assert snap["serve_errors"] == 1 + len(calls)
    assert snap.get("serve_batches", 0) == 0
    assert snap.get("serve_prefills", 0) == 0
    stats = a.stats()
    assert stats["live_slots"] == 0 and stats["free_slots"] == model.slots
    with pytest.raises(RuntimeError, match="unknown episode slot"):
        a.step(OBS)
    # and it admits again on the new pool
    again = _Call(newcomer.reset, PREFIX)
    _until(lambda: len(model.prefills) == 2, "a prefill on the new pool")
    model.prefills[1][1].gate.set()
    assert again.result()["pos"] == 3
    assert counters.get("serve_pool_rebuilds") == 1
    newcomer.close()


def test_a_snapshot_staged_with_a_prefill_in_flight_is_adopted_after_it():
    model, counters = StubModel(), EventCounters()
    with start_server_thread(model, counters=counters, tick_ms=2.0) as h:
        a, b = _client(h), _client(h)
        a.reset()
        bus = h.server.subscriber = _StagedWhileFlying(h.server)
        _until(lambda: h.server.weight_version == 1, "version 1")
        admitted = _Call(b.reset, PREFIX)
        _until(lambda: len(model.prefills) == 1, "the prefill")
        # the snapshot is staged with the prefill in flight; it is not
        # adopted until the reset has been answered
        _until(lambda: bus.staged_with_flying, "version 2 to be staged")
        time.sleep(0.05)
        assert h.server.weight_version == 1 and model.w == 10.0
        model.prefills[0][1].gate.set()
        out = admitted.result()
        assert out["weight_version"] == 1
        assert out["pred"] == 40.0  # executed under version 1
        _until(lambda: h.server.weight_version == 2, "version 2")
        b.close_episode()
        nxt = _Call(b.reset, PREFIX)
        _until(lambda: len(model.prefills) == 2, "the next prefill")
        model.prefills[1][1].gate.set()
        out = nxt.result()
        assert out["weight_version"] == 2 and out["pred"] == 400.0
        assert counters.get("weight_adopted") == 2
        a.close()
        b.close()


def test_a_slot_refilled_in_flight_is_rewound_behind_the_old_step(
        served):
    model, counters, h, a, b = served
    slot, episode = a.slot, a.episode
    bystander = _client(h)  # so that somebody can still send afterwards
    bystander.reset()
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    janitor, tenant = _client(h), _client(h)
    assert janitor.rpc("close", {"slot": slot, "episode": episode})["closed"]
    admitted = _Call(tenant.reset, PREFIX)
    _until(lambda: len(model.prefills) == 1, "the tenant's prefill")
    assert model.prefills[0][0] == slot
    # rewind and prefill are ordered behind the step that was in flight
    assert model.log[-3:] == [("step", (slot,)), ("reset", (slot,)),
                              ("prefill", (slot,))]
    model.calls[0][1].gate.set()
    out = call.result()
    assert out["pred"][0] == 3.0 and out["pos"] == 0  # the old step's answer
    model.prefills[0][1].gate.set()
    reply = admitted.result()
    assert reply["slot"] == slot and reply["episode"] != episode
    with pytest.raises(RuntimeError, match="stale episode lease"):
        a.step(OBS)  # a's lease is gone: it cannot step the new tenant
    nxt = _Call(tenant.step, OBS)
    _until(lambda: len(model.calls) == 2, "the tenant's tick")
    model.calls[1][1].gate.set()
    assert nxt.result()["pos"] == 3
    for c in (janitor, tenant, bystander):
        c.close()


def test_a_model_whose_prefill_returns_an_array_is_answered_at_once():
    """``LinearModel`` and stubs: an array is a reply that is always
    ready, so nothing is launched for it and nothing deferred."""
    from blendjax.serve.server import LinearModel

    counters = EventCounters()
    with start_server_thread(LinearModel(obs_dim=2, slots=2),
                             counters=counters) as h:
        c = _client(h)
        reply = c.reset(prefix=PREFIX)
        assert reply["pos"] == 3 and not h.server._launched
        assert c.step(OBS)["pos"] == 3
        snap = counters.snapshot()
        assert snap["serve_prefills"] == 1 and snap["serve_resets"] == 1
        assert snap.get("serve_prefills_overlapped", 0) == 0
        c.close()


# -- the model's half: a reply is fetched when asked, a lost pool once -------


def _tiny_model(slots=4):
    import jax

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel

    params = seqformer.init(jax.random.PRNGKey(0), obs_dim=5, d_model=32,
                            n_heads=4, n_layers=2, max_len=32)
    return params, SeqFormerModel(params, slots=slots, length=16)


def test_step_rows_returns_an_unfenced_reply_that_fetches_once():
    _, model = _tiny_model()
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((2, 5), np.float32)
    idx = np.asarray([0, 1])
    first = model.step_rows(idx, obs)
    second = model.step_rows(idx, obs)  # behind it, nothing fetched yet
    assert not isinstance(first, np.ndarray)
    rows = np.array(first)              # the benchmark's fault calls it so
    rows[0, 0] += 1.0                   # a copy: the reply keeps its rows
    np.testing.assert_array_equal(np.asarray(first)[0], first[0])
    assert first.is_ready() and np.asarray(first)[0, 0] != rows[0, 0]
    assert np.asarray(second).shape == (2, 5)
    assert not np.array_equal(np.asarray(first), np.asarray(second))


class _Poisoned:
    """What a step that failed on the device hands back."""

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device fault (injected)")


def test_two_replies_off_one_lost_pool_cost_one_rebuild():
    _, model = _tiny_model()
    rng = np.random.default_rng(1)
    obs = rng.standard_normal((2, 5), np.float32)
    idx = np.asarray([0, 1])
    np.asarray(model.step_rows(idx, obs))
    real = model._step
    model._step = lambda *args: (_Poisoned(), real(*args)[1])
    first = model.step_rows(idx, obs)
    second = model.step_rows(idx, obs)
    model._step = real
    with pytest.raises(SlotPoolLost, match="device fault"):
        np.asarray(first)
    assert model.pool_rebuilds == 1
    with pytest.raises(RuntimeError, match="device fault") as caught:
        np.asarray(second)
    assert not isinstance(caught.value, SlotPoolLost)
    assert model.pool_rebuilds == 1
    # the rebuilt pool serves: a fresh model's answer for a first step
    _, fresh = _tiny_model()
    np.testing.assert_array_equal(np.asarray(model.step_rows(idx, obs)),
                                  np.asarray(fresh.step_rows(idx, obs)))


# -- real models: concurrent closed-loop clients against serial decode -------


def _drive(model, episodes, rounds, *, max_batch, prefix_of=None):
    """``len(episodes)`` closed-loop clients, each running its
    ``rounds`` episodes one after another (the first ``prefix_of(i, r)``
    positions of an episode admitted as the reset's prefix, the reply's
    prediction first among its outputs); returns the predictions per
    client and episode, and the server's counters."""
    counters = EventCounters()
    outs = [[[] for _ in range(rounds)] for _ in episodes]
    errors = []
    with start_server_thread(model, counters=counters, tick_ms=1.0,
                             max_batch=max_batch) as h:
        def run(i):
            c = ServeClient(h.address, timeoutms=60000)
            try:
                for r in range(rounds):
                    ep = episodes[i][r]
                    t0 = prefix_of(i, r) if prefix_of else 0
                    if t0:
                        reply = c.reset(prefix=ep[:t0])
                        assert reply["pos"] == t0
                        outs[i][r].append(reply["pred"])
                    else:
                        c.reset()
                    for t in range(t0, len(ep)):
                        outs[i][r].append(c.step(ep[t])["pred"])
                    assert c.close_episode() is True
            except Exception as exc:  # noqa: BLE001 - handed to the test
                errors.append(exc)
            finally:
                c.close()

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(len(episodes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    return outs, counters.snapshot()


def test_concurrent_clients_get_what_serial_decode_gives():
    import functools

    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer

    params, model = _tiny_model(slots=6)
    rng = np.random.default_rng(2)
    episodes = [[rng.standard_normal((3 + (i + r) % 5, 5), np.float32)
                 for r in range(3)] for i in range(6)]
    outs, snap = _drive(model, episodes, 3, max_batch=4)
    step = jax.jit(functools.partial(seqformer.decode_step,
                                     compute_dtype=jnp.float32))
    for i, client in enumerate(episodes):
        for r, ep in enumerate(client):
            cache = seqformer.init_cache(params, 1, dtype=jnp.float32,
                                         length=16)
            for t in range(len(ep)):
                want, cache = step(params, cache, jnp.asarray(ep[t][None]))
                np.testing.assert_allclose(outs[i][r][t], want[0],
                                           atol=1e-5, rtol=1e-5)
    steps = sum(len(ep) for client in episodes for ep in client)
    assert snap["serve_replies"] >= steps
    assert snap["serve_batches"] < steps  # they were batched
    assert snap.get("serve_errors", 0) == 0
    assert snap.get("serve_pool_rebuilds", 0) == 0
    assert "serve_ticks_overlapped" in snap


def _tiny_linear_attention_model(slots):
    """Two periods of three gated delta-rule layers (a float32 matrix
    state a head, convolution tails) and one full-attention layer: what
    a reset has to zero, and a prefill goes on from."""
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import SeqFormerModel
    from chipbench import reference_olmohybrid as ref

    widths = dict(
        hidden_size=64, intermediate_size=128, num_hidden_layers=8,
        num_attention_heads=2, num_key_value_heads=2,
        layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rms_norm_eps=1e-6, vocab_size=128, tie_word_embeddings=False)
    served = seqformer.describe_token_model(
        ref.make_params(widths, 5, jnp.float32), widths)

    def build():
        return SeqFormerModel(served, slots=slots, length=32,
                              compute_dtype=jnp.float32,
                              cache_dtype=jnp.float32)

    rng = np.random.default_rng(6)
    return build, lambda n: rng.integers(0, 128, (n, 1)).astype(np.int32)


def _tiny_seqformer(slots):
    params, _ = _tiny_model()
    from blendjax.serve.server import SeqFormerModel

    rng = np.random.default_rng(7)
    return (lambda: SeqFormerModel(params, slots=slots, length=16),
            lambda n: rng.standard_normal((n, 5), np.float32))


@pytest.mark.parametrize("tiny,atol", [
    (_tiny_seqformer, 1e-5),
    # through eight layers with a norm after each, a batched step lies
    # up to ~1e-3 from the row stepped alone; a state left behind by the
    # slot's last tenant reads above 0.05 (test_linear_attention_model.py)
    (_tiny_linear_attention_model, 5e-3),
], ids=["seqformer", "recurrent_state"])
def test_prefixed_resets_among_steppers_get_what_a_fenced_twin_gives(
        tiny, atol):
    """Every slot is reused twice, every reset (a rewind, and for the
    recurrent model a zeroing of state) and its prefill dispatched
    behind whatever tick the others have in flight; the twin fences
    each call where it makes it."""
    n, rounds = 5, 3
    build, draw = tiny(n)
    episodes = [[draw(6 + (2 * i + 3 * r) % 7) for r in range(rounds)]
                for i in range(n)]

    def prefix_of(i, r):
        return 2 + (i + r) % 4

    outs, snap = _drive(build(), episodes, rounds, max_batch=4,
                        prefix_of=prefix_of)
    twin, row = build(), np.asarray([0])
    for i, client in enumerate(episodes):
        for r, ep in enumerate(client):
            t0 = prefix_of(i, r)
            twin.reset_rows(row)
            want = [twin.prefill_rows(row, ep[:t0])]
            want += [np.asarray(twin.step_rows(row, ep[t][None]))[0]
                     for t in range(t0, len(ep))]
            assert len(outs[i][r]) == len(want)
            for got, ref in zip(outs[i][r], want):
                np.testing.assert_allclose(got, ref, atol=atol, rtol=atol)
    assert snap["serve_prefills"] == snap["serve_resets"] == n * rounds
    assert 1 <= snap["serve_prefills_overlapped"] <= n * rounds
    assert snap.get("serve_errors", 0) == 0
    assert snap.get("serve_pool_rebuilds", 0) == 0
    if "serve_state_resets" in snap:
        assert snap["serve_state_resets"] == n * rounds


def test_concurrent_token_clients_and_the_routed_counts_match_serial():
    import jax
    import jax.numpy as jnp

    from blendjax.models import seqformer
    from blendjax.serve.server import (
        MOE_EVENTS,
        TOKEN_REPLY_TOP,
        SeqFormerModel,
    )

    widths = dict(
        hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        rope_theta=10000, rope_scaling=None, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=64,
        moe_intermediate_size=16, num_experts=16, num_experts_per_tok=4,
        routed_scaling_factor=2.5, num_shared_experts=1, vocab_size=64)
    served = seqformer.init_token_model(jax.random.PRNGKey(3), widths,
                                        held=(4, 8))
    model = SeqFormerModel(served, slots=6, length=16,
                           compute_dtype=jnp.float32)
    rng = np.random.default_rng(4)
    episodes = [[rng.integers(0, 64, (3 + (i + 2 * r) % 4, 1)).astype(
        np.int32) for r in range(3)] for i in range(6)]
    outs, snap = _drive(model, episodes, 3, max_batch=4)

    @jax.jit
    def step(cache, ids):
        pred, cache, auxs = seqformer._decode(
            served, cache, ids, compute_dtype=jnp.float32,
            slots=jnp.zeros(1, jnp.int32), valid=jnp.ones(1, bool))
        return pred[0], cache, sum(a["counts"] for a in auxs)

    k = TOKEN_REPLY_TOP
    counts = np.zeros(3, np.int64)
    for i, client in enumerate(episodes):
        for r, ep in enumerate(client):
            cache = seqformer.init_cache(served, 1, dtype=jnp.float32,
                                         length=16, per_row=True)
            for t in range(len(ep)):
                logits, cache, made = step(cache, jnp.asarray(ep[t]))
                counts += np.asarray(made)
                logits = np.asarray(logits)
                got = outs[i][r][t]
                order = np.argsort(-logits)[:k]
                np.testing.assert_array_equal(got[k:2 * k].astype(int),
                                              order)
                np.testing.assert_allclose(got[:k], logits[order],
                                           atol=2e-5)
                np.testing.assert_allclose(
                    got[-1], jax.nn.logsumexp(logits), atol=2e-5)
    # assignments made and held are sums over rows: the batching cannot
    # move them; the distinct experts hit are counted a tick, so rows
    # stepped together share them
    made, held, hit = (snap[name] for name in MOE_EVENTS)
    assert [made, held] == list(counts[:2])
    assert 0 < hit < counts[2]
    assert snap.get("serve_errors", 0) == 0
