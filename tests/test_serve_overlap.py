"""One serve tick in flight (docs/serving.md "One tick in flight").

``PolicyServer`` launches a tick (assemble + dispatch) and retires it
(fetch + replies) as two halves, and keeps at most one launched tick
between two turns of its loop, so admission, the next launch and the
older tick's replies run beside the device.  Locked here, on the CPU,
with a stub model whose replies become ready when the test says so: the
overlap itself and its counter, the lone client's unchanged sequence,
and the guarantees the overlap could break — exactly-once, the version
stamps, the lost pool, the leases.  Then real models: concurrent
closed-loop clients get exactly what serial decode gives.

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
    the reply is handed out gated.  ``calls`` holds (real rows, reply)
    in dispatch order, ``log`` every call that touched a row."""

    kind = "stub"
    obs_dim = 2

    def __init__(self, slots=4):
        self.slots = self.pad_slot = slots
        self.pos = np.zeros(slots + 1, np.int64)
        self.w = 1.0
        self.calls = []
        self.log = []
        self.errors = {}  # call number -> what its fetch raises
        self.pool_rebuilds = 0

    def reset_rows(self, idx):
        self.pos[idx] = 0
        self.log.append(("reset", tuple(int(i) for i in idx)))

    def apply_weights(self, tree):
        self.w = float(tree["w"])

    def prefill_rows(self, idx, prefix):
        self.pos[idx] = len(prefix)
        self.log.append(("prefill", tuple(int(i) for i in idx),
                         [reply.gate.is_set() for _, reply in self.calls]))
        return prefix[-1:].sum(-1) * np.float32(self.w)

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
            for _, reply in model.calls:
                reply.gate.set()
            a.close()
            b.close()


OBS = np.asarray([1.0, 2.0], np.float32)


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
    assert snap["serve_fetch_wait_us"] > 0
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


def test_a_prefill_waits_for_no_launched_ticks_answers(served):
    """A prefill blocks the server's thread: whatever was launched is
    answered before it starts, as when prefills ran between ticks."""
    model, counters, h, a, b = served
    call = _Call(a.step, OBS)
    _until(lambda: len(model.calls) == 1, "a's tick")
    newcomer = _client(h)
    admitted = _Call(newcomer.reset, np.ones((3, 2), np.float32))
    # the reset is read with a's tick in flight and goes to its fetch
    assert model.calls[0][1].fetching.wait(WAIT_S)
    assert not [e for e in model.log if e[0] == "prefill"]
    model.calls[0][1].gate.set()
    assert call.result()["pos"] == 0
    reply = admitted.result()
    assert reply["pos"] == 3
    (prefill,) = [e for e in model.log if e[0] == "prefill"]
    assert prefill[2] == [True]  # a's reply had been fetched by then
    assert counters.get("serve_prefills") == 1
    newcomer.close()


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
    ``rounds`` episodes one after another; returns the predictions per
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
                    c.reset()
                    for t in range(len(ep)):
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
