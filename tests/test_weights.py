"""WeightBus tests (docs/weight_bus.md).

The load-bearing ones: a hot-swap between ticks must preserve episode
leases, KV positions and the exactly-once reply cache (the LinearModel
position witness makes a half-applied or double-applied swap visible);
a torn or digest-mismatched snapshot must be discarded — never
half-applied — with the server still serving the last good version
through a publisher SIGKILL; and the gateway's canary routing must be
version-gated, promoted by a healthy window and rolled back by a
metric regression (the controller's verdicts are driven by REAL
per-version latency stats, not injected state).
"""

import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from blendjax.btt.faults import FaultPolicy
from blendjax.utils.timing import (
    WEIGHT_EVENTS,
    WEIGHT_STAGES,
    EventCounters,
    StageTimer,
)
from blendjax.weights.bus import (
    WeightPublisher,
    WeightSubscriber,
    linear_tree,
)
from blendjax.weights.snapshot import (
    Snapshot,
    SnapshotAssembler,
    flatten_tree,
    snapshot_messages,
    unflatten_tree,
)


def _weight_counts(counters):
    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("weight_")}


def _wait(predicate, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _poll_snapshot(sub, timeout=10.0, msg="a snapshot"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = sub.poll()
        if snap is not None:
            return snap
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# snapshot layer
# ---------------------------------------------------------------------------


def test_flatten_unflatten_roundtrip():
    tree = {
        "embed": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "blocks": [
            {"wq": {"w": np.ones((2, 2), np.float32)}},
            {"wq": {"w": np.zeros((2, 2), np.int8)}},
        ],
        "scalar": np.float32(3.5),
    }
    flat = flatten_tree(tree)
    assert "blocks/#0/wq/w" in flat and "embed/w" in flat
    back = unflatten_tree(flat)
    assert isinstance(back["blocks"], list) and len(back["blocks"]) == 2
    np.testing.assert_array_equal(back["embed"]["w"], tree["embed"]["w"])
    assert back["blocks"][1]["wq"]["w"].dtype == np.int8
    np.testing.assert_array_equal(np.asarray(back["scalar"]),
                                  np.float32(3.5))


def test_snapshot_messages_roundtrip_and_delta():
    rng = np.random.default_rng(0)
    t1 = {"a": rng.standard_normal((16, 16)).astype(np.float32),
          "b": rng.standard_normal(8).astype(np.float32)}
    s1 = Snapshot.from_params(t1, 1, step=10)
    asm = SnapshotAssembler()
    got = None
    for m in snapshot_messages(s1, chunk_bytes=64):
        snap, reason = asm.feed(m)
        assert reason is None, reason
        got = snap or got
    assert got is not None and got.version == 1 and got.step == 10
    np.testing.assert_array_equal(got.tree()["a"], t1["a"])
    # delta: only the changed leaf ships, the other is carried by path
    t2 = {"a": t1["a"], "b": t1["b"] + 1.0}
    s2 = Snapshot.from_params(t2, 2, step=11)
    msgs = snapshot_messages(s2, prev=s1, chunk_bytes=64)
    assert msgs[0]["carry"] == ["a"] and msgs[0]["base"] == 1
    assert [m[0] for m in msgs[0]["manifest"]] == ["b"]
    got = None
    for m in msgs:
        snap, reason = asm.feed(m)
        assert reason is None, reason
        got = snap or got
    assert got is not None and got.version == 2
    np.testing.assert_array_equal(got.tree()["a"], t1["a"])
    np.testing.assert_array_equal(got.tree()["b"], t1["b"] + 1.0)


def test_assembler_discards_torn_gapped_and_mismatched_streams():
    rng = np.random.default_rng(1)
    tree = {"w": rng.standard_normal((8, 8)).astype(np.float32)}
    mk = lambda v: snapshot_messages(Snapshot.from_params(tree, v),
                                     chunk_bytes=32)
    asm = SnapshotAssembler()
    # a superseding begin tears the in-flight assembly
    m1 = mk(1)
    asm.feed(m1[0])
    asm.feed(m1[1])
    m2 = mk(2)
    snap, reason = asm.feed(m2[0])
    assert snap is None and reason == "torn"
    for m in m2[1:]:
        snap, reason = asm.feed(m)
        assert reason is None
    assert snap.version == 2 and asm.version == 2
    # a sequence gap tears
    m3 = mk(3)
    asm.feed(m3[0])
    asm.feed(m3[1])
    snap, reason = asm.feed(m3[3])  # skipped seq 1
    assert snap is None and reason == "torn"
    # stale versions (a dead publisher's leftovers) never adopt
    snap, reason = asm.feed(mk(1)[0])
    assert snap is None and asm._cur is None
    # a garbled chunk fails the stream digest, never half-applies
    m4 = mk(4)
    bad = dict(m4[1])
    bad["data"] = np.asarray(bad["data"]).copy()
    bad["data"][0] ^= 0xFF
    asm.feed(m4[0])
    asm.feed(bad)
    for m in m4[2:-1]:
        asm.feed(m)
    snap, reason = asm.feed(m4[-1])
    assert snap is None and reason == "digest"
    assert asm.version == 2  # still the last GOOD snapshot
    # a delta whose base we do not hold asks for a full sync
    s5 = Snapshot.from_params({"w": tree["w"] + 1}, 5)
    s6 = Snapshot.from_params({"w": tree["w"] + 1, }, 6)
    delta = snapshot_messages(s6, prev=s5, chunk_bytes=32)
    assert delta[0]["carry"]
    snap, reason = asm.feed(delta[0])
    assert snap is None and reason == "need_full"


def test_quantize_for_wire_dispatch():
    import jax

    from blendjax.models import policy
    from blendjax.ops.quant import quantize_for_wire

    params = policy.init(jax.random.PRNGKey(0), 4, 3)
    assert quantize_for_wire(params, None) is params
    q = quantize_for_wire(params, "policy")
    assert "w_q" in q["layers"][0]
    with pytest.raises(ValueError, match="unknown wire-quantization"):
        quantize_for_wire(params, "frobnicator")
    # the quantized tree survives the snapshot wire bit-exactly
    flat = flatten_tree(jax.device_get(q))
    back = unflatten_tree(flat)
    np.testing.assert_array_equal(
        np.asarray(back["layers"][0]["w_q"]),
        np.asarray(q["layers"][0]["w_q"]),
    )


# ---------------------------------------------------------------------------
# publisher <-> subscriber
# ---------------------------------------------------------------------------


def test_late_joiner_syncs_then_rides_pushes_and_rollback_republish():
    counters = EventCounters()
    with WeightPublisher(counters=counters, history=4).start() as pub:
        v1 = pub.publish(linear_tree(1, 4), step=1)
        # late joiner: v1 was published before this subscriber existed
        sub = WeightSubscriber(pub.address, counters=counters)
        try:
            snap = _poll_snapshot(sub, msg="late-joiner sync")
            assert snap.version == v1
            np.testing.assert_array_equal(
                snap.tree()["w"], linear_tree(1, 4)["w"]
            )
            # registered now: the next publish is PUSHED
            v2 = pub.publish(linear_tree(2, 4), step=2)
            assert _poll_snapshot(sub, msg="pushed v2").version == v2
            # rollback republish: v1's weights under a fresh higher id
            v3 = pub.republish(v1)
            assert v3 > v2
            snap = _poll_snapshot(sub, msg="republished v1 weights")
            assert snap.version == v3
            np.testing.assert_array_equal(
                snap.tree()["w"], linear_tree(1, 4)["w"]
            )
            snap = _weight_counts(counters)
            assert snap["weight_published"] == 3
            assert snap["weight_rollback_publishes"] == 1
            assert snap["weight_syncs"] >= 1
            # versions acked back: the publisher knows its fleet is
            # caught up
            _wait(lambda: v3 in pub.subscribers.values(),
                  msg="ack of v3")
            with pytest.raises(KeyError, match="not in publisher"):
                pub.republish(999)
        finally:
            sub.close()


def test_slow_stream_suppresses_resync_no_duplicate_syncs_or_tears():
    """A snapshot stream slower than the resync interval must not be
    re-requested mid-assembly: the keepalive sync is suppressed while
    chunks are in flight (``SnapshotAssembler.in_flight``), so the
    publisher never streams a duplicate full snapshot and nothing is
    torn — the stall timeout alone owns dead-mid-stream publishers."""
    counters = EventCounters()
    with WeightPublisher(counters=counters, chunk_bytes=2048,
                         chunk_sleep_ms=25).start() as pub:
        sub = WeightSubscriber(pub.address, counters=counters,
                               resync_interval_s=0.05,
                               stall_timeout_s=10.0)
        try:
            _wait(lambda: (sub.poll(), len(pub.subscribers))[-1] >= 1,
                  msg="subscriber announced")
            # adopt a v1 and let every pre-publish wb_sync get its
            # answer, so the sync counter baseline below is settled
            v1 = pub.publish(linear_tree(1, 4))
            assert _poll_snapshot(sub, msg="v1").version == v1
            settle = time.monotonic() + 0.15
            while time.monotonic() < settle:
                sub.poll()
                time.sleep(0.01)
            baseline = _weight_counts(counters).get("weight_syncs", 0)
            # arm the keepalive WITHOUT sending (a sent sync could sit
            # queued behind the publish and be answered after it), then
            # stream v2: ~10 chunks x 25ms sleep spans ~5 resync
            # intervals — every one of them must be suppressed by the
            # in-flight assembly
            sub._next_sync = time.monotonic() + 0.05
            tree = {"w": np.arange(5000, dtype=np.float32)}
            t = threading.Thread(target=pub.publish, args=(tree,),
                                 daemon=True)
            t.start()
            snap = _poll_snapshot(sub, msg="slow-streamed snapshot")
            t.join(timeout=5)
            np.testing.assert_array_equal(snap.tree()["w"], tree["w"])
            snap_counts = _weight_counts(counters)
            # no mid-stream wb_sync was answered with a full stream,
            # and nothing tore
            assert snap_counts.get("weight_syncs", 0) == baseline, \
                (baseline, snap_counts)
            assert snap_counts.get("weight_torn_discarded", 0) == 0
        finally:
            sub.close()


def test_publisher_lru_refreshes_live_subscribers(monkeypatch):
    """Subscriber-table cap eviction is LRU: a live, acking subscriber
    refreshes its age with every sync/ack, so churn of newer idents
    evicts the stalest entry — never the active one."""
    from blendjax.weights import bus as bus_mod

    monkeypatch.setattr(bus_mod, "SUBSCRIBER_CAP", 2)
    counters = EventCounters()
    with WeightPublisher(counters=counters).start() as pub:
        s1 = WeightSubscriber(pub.address, counters=counters)
        s2 = WeightSubscriber(pub.address, counters=counters)
        s3 = WeightSubscriber(pub.address, counters=counters)
        try:
            s1.request_sync()
            _wait(lambda: len(pub.subscribers) == 1, msg="s1 announced")
            s2.request_sync()
            _wait(lambda: len(pub.subscribers) == 2, msg="s2 announced")
            # s1 adopts + acks v1: its entry refreshes to newest, so
            # the stalest is now s2
            v1 = pub.publish(linear_tree(1, 4))
            assert _poll_snapshot(s1, msg="s1 at v1").version == v1
            _wait(lambda: v1 in pub.subscribers.values(),
                  msg="s1's ack refreshed its entry")
            s3.request_sync()
            _wait(lambda: len(pub.subscribers) == 2, msg="cap held")
            # without LRU refresh the insertion-oldest (s1 — the live,
            # acking one) would have been evicted
            assert v1 in pub.subscribers.values(), pub.subscribers
        finally:
            for s in (s1, s2, s3):
                s.close()


# ---------------------------------------------------------------------------
# the server hot-swap (tentpole)
# ---------------------------------------------------------------------------


def test_hot_swap_preserves_leases_positions_and_stamps_version():
    """THE swap contract: a live episode's slot, lease and position
    survive the between-ticks hot-swap — predictions change weights
    mid-episode with the position counter continuing, and every reply
    after adoption is stamped ``weight_version`` (none before)."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread
    from blendjax.serve.client import ServeRPCError

    counters, timer = EventCounters(), StageTimer()
    obs = np.arange(4, dtype=np.float32)
    w0 = np.random.default_rng(0).standard_normal((4, 4)).astype(
        np.float32
    )
    with WeightPublisher(counters=counters).start() as pub:
        h = start_server_thread(
            LinearModel(obs_dim=4, slots=4, seed=0),
            counters=counters, timer=timer,
            subscriber=WeightSubscriber(pub.address),
        )
        try:
            c = ServeClient(h.address)
            c.reset()
            slot, episode = c.slot, c.episode
            for k in range(3):
                r = c.step(obs)
                assert "weight_version" not in r  # bus-less so far
                np.testing.assert_allclose(
                    r["pred"], obs @ w0 + np.float32(k), rtol=1e-5
                )
            assert c.weight_version is None
            v1 = pub.publish(linear_tree(101, 4))
            w1 = linear_tree(101, 4)["w"]
            seen = []
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                r = c.step(obs)
                seen.append(r)
                if r.get("weight_version") == v1:
                    break
            assert seen[-1].get("weight_version") == v1, \
                "swap never observed"
            # the SAME episode: lease untouched, position continued
            assert (c.slot, c.episode) == (slot, episode)
            for r in seen:
                w = w1 if r.get("weight_version") == v1 else w0
                np.testing.assert_allclose(
                    r["pred"], obs @ w + np.float32(r["pos"]), rtol=1e-5
                )
            assert [r["pos"] for r in seen] == \
                list(range(3, 3 + len(seen)))
            assert c.weight_version == v1
            # telemetry carries the version (what the gateway scrapes)
            assert c.telemetry()["weight_version"] == v1
            snap = _weight_counts(counters)
            assert snap["weight_adopted"] == 1
            assert timer.summary()["weight_swap"]["count"] == 1
            # a transport error now names the version alongside the
            # address — a bad rollout is diagnosable from the traceback
            h.close()
            c.policy = FaultPolicy(max_retries=0, circuit_threshold=0)
            c.state = c.policy.new_state()
            with pytest.raises(ServeRPCError, match=r"weights v\d+"):
                c.step(obs, timeout_ms=200)
            c.close()
        finally:
            h.close()


def test_multi_model_subscriber_targets_and_stamps_per_model():
    """A multi-model server routes an unstamped snapshot to the model
    its SUBSCRIBER was attached for, and stamps every reply with the
    EXECUTING model's version — a co-hosted model the bus never
    updated keeps its startup weights and stays unstamped (its traffic
    must not be attributed to another model's rollout)."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with WeightPublisher(counters=counters).start() as pub:
        with start_server_thread(
            {
                "a": LinearModel(obs_dim=4, slots=2, seed=0),
                "b": LinearModel(obs_dim=4, slots=2, seed=7),
            },
            counters=counters,
            subscriber=WeightSubscriber(pub.address, model="b"),
        ) as h:
            ca = ServeClient(h.address, model="a")
            cb = ServeClient(h.address, model="b")
            try:
                ca.reset()
                cb.reset()
                # no model stamp on the snapshot: the subscriber's
                # model= routes it into "b"
                v = pub.publish(linear_tree(11, 4))
                wb = linear_tree(11, 4)["w"]
                _wait(lambda: cb.step(obs).get("weight_version") == v,
                      msg="model b at published version")
                rb = cb.step(obs)
                np.testing.assert_allclose(
                    rb["pred"], obs @ wb + np.float32(rb["pos"]),
                    rtol=1e-5,
                )
                assert cb.weight_version == v
                # model "a": untouched weights, no version stamp
                ra = ca.step(obs)
                assert "weight_version" not in ra, ra
                assert ca.weight_version is None
                np.testing.assert_allclose(
                    ra["pred"],
                    obs @ LinearModel(obs_dim=4, slots=2, seed=0).w
                    + np.float32(ra["pos"]),
                    rtol=1e-5,
                )
            finally:
                ca.close()
                cb.close()


def test_apply_failure_keeps_last_good_version():
    """A published snapshot the model refuses (shape drift) must cost a
    counter, not the serving weights."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with WeightPublisher(counters=counters).start() as pub:
        with start_server_thread(
            LinearModel(obs_dim=4, slots=2, seed=0),
            counters=counters,
            subscriber=WeightSubscriber(pub.address),
        ) as h:
            c = ServeClient(h.address)
            c.reset()
            v1 = pub.publish(linear_tree(7, 4))
            _wait(lambda: c.step(obs).get("weight_version") == v1,
                  msg="v1 adoption")
            pub.publish(linear_tree(8, 6))  # wrong obs_dim: refused
            _wait(lambda: _weight_counts(counters).get(
                "weight_apply_failed", 0) >= 1, msg="apply failure")
            r = c.step(obs)
            assert r["weight_version"] == v1  # still the last good
            np.testing.assert_allclose(
                r["pred"],
                obs @ linear_tree(7, 4)["w"] + np.float32(r["pos"]),
                rtol=1e-5,
            )
            c.close()


def test_exactly_once_retry_across_a_swap_served_from_cache():
    """A FaultPolicy retry whose original executed BEFORE the swap is
    answered from the reply cache — stamped with the version that
    actually executed it — and the position advances exactly once, so
    the swap cannot double-apply (or re-apply at the new version) an
    acked step."""
    from blendjax.btt.chaos import ChaosProxy
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    with WeightPublisher(counters=counters,
                         version_base=0).start() as pub:
        with start_server_thread(
            LinearModel(obs_dim=4, slots=2, seed=0),
            counters=counters,
            subscriber=WeightSubscriber(pub.address),
        ) as h:
            v1 = pub.publish(linear_tree(21, 4))
            w1 = linear_tree(21, 4)["w"]
            with ChaosProxy(h.address) as proxy:
                c = ServeClient(
                    proxy.address, shm=False, timeoutms=400,
                    fault_policy=FaultPolicy(
                        max_retries=3, backoff_base=0.02,
                        backoff_max=0.1, circuit_threshold=0, seed=3,
                    ),
                    counters=counters,
                )
                c.reset()
                _wait(lambda: c.step(obs).get("weight_version") == v1,
                      msg="v1 adoption")
                k = c.step(obs)["pos"] + 1
                # lose the next reply; publish v2 while the client is
                # still waiting on the original (already executed at v1)
                proxy.drop_next("down")
                swap = threading.Thread(
                    target=lambda: (time.sleep(0.05),
                                    pub.publish(linear_tree(22, 4))),
                    daemon=True,
                )
                swap.start()
                r = c.step(obs)
                swap.join()
                # the cached reply: executed at v1, stamped v1 — NOT
                # re-executed at v2
                assert r["weight_version"] == v1, r
                assert r["pos"] == k
                np.testing.assert_allclose(
                    r["pred"], obs @ w1 + np.float32(k), rtol=1e-5
                )
                assert counters.snapshot().get("serve_cache_hits",
                                               0) >= 1
                # and the NEXT step runs at v2 with the position having
                # advanced exactly once through the whole episode
                w2 = linear_tree(22, 4)["w"]
                r2 = c.step(obs)
                deadline = time.monotonic() + 5
                while r2.get("weight_version") != 2 \
                        and time.monotonic() < deadline:
                    r2 = c.step(obs)
                assert r2["weight_version"] == 2
                np.testing.assert_allclose(
                    r2["pred"], obs @ w2 + np.float32(r2["pos"]),
                    rtol=1e-5,
                )
                c.close()


def test_quantized_snapshot_serves_int8_policy():
    """The wire-quantization path: a ``quantize='policy'`` publisher
    feeds an ``--int8`` policy server (same precision end to end), and
    a float snapshot against the int8 server is refused — counted, not
    half-applied."""
    import jax

    from blendjax.models import policy
    from blendjax.serve import PolicyModel, ServeClient, start_server_thread

    counters = EventCounters()
    params = policy.init(jax.random.PRNGKey(0), 4, 3)
    trained = jax.tree.map(lambda a: a * 0.5, params)
    with WeightPublisher(quantize="policy",
                         counters=counters).start() as pub:
        with start_server_thread(
            PolicyModel(params, 4, int8=True), counters=counters,
            subscriber=WeightSubscriber(pub.address),
        ) as h:
            c = ServeClient(h.address)
            c.reset()
            obs = np.arange(4, dtype=np.float32)
            v1 = pub.publish(jax.device_get(trained), step=5)
            _wait(lambda: c.step(obs).get("weight_version") == v1,
                  msg="quantized adoption")
            # the adopted weights ARE the quantized publish: the served
            # logits match quantize_policy(trained) through the same
            # int8 dispatch the --int8 CLI serves (numeric parity of
            # quantize_policy itself is locked in test_serve)
            from blendjax.ops.quant import quantize_policy

            want = np.asarray(policy.logits(
                quantize_policy(jax.tree.map(jax.numpy.asarray,
                                             trained)), obs[None]
            ))
            got = h.server.model.step_rows(np.asarray([0]), obs[None])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            # a FLOAT snapshot against the int8 server is refused at
            # the apply seam — precision routing, never a silent
            # wrong-precision swap
            with pytest.raises(ValueError, match="float snapshot"):
                h.server.model.apply_weights(jax.device_get(trained))
            r = c.step(obs)
            assert r["weight_version"] == v1  # still the quantized one
            c.close()


# ---------------------------------------------------------------------------
# gateway canary + controller
# ---------------------------------------------------------------------------


def _episode(gw_address, obs_dim=4, steps=3, timeoutms=4000):
    """One fresh episode through the gateway; returns (replica id,
    weight_version seen, step latencies)."""
    from blendjax.serve import ServeClient

    c = ServeClient(gw_address, timeoutms=timeoutms)
    try:
        c.reset()
        obs = np.zeros(obs_dim, np.float32)
        vs = []
        for _ in range(steps):
            vs.append(c.step(obs).get("weight_version"))
        c.close_episode()
        return c.replica, vs
    finally:
        c.close()


def test_controller_promotes_after_healthy_window():
    """Fleet-wide rollout: both replicas subscribe, a new version
    appears, the controller opens a canary window, real traffic
    accumulates per-version stats, and the healthy window promotes —
    ``stable_version`` follows the publisher."""
    from blendjax.serve import LinearModel, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread
    from blendjax.weights.controller import WeightBusController

    counters = EventCounters()
    with WeightPublisher(counters=counters).start() as pub:
        servers = [
            start_server_thread(
                LinearModel(obs_dim=4, slots=8, seed=0),
                counters=EventCounters(),
                subscriber=WeightSubscriber(pub.address,
                                            counters=counters),
            )
            for _ in range(2)
        ]
        gw = start_gateway_thread(
            [s.address for s in servers], counters=counters,
            scrape_interval_s=0.1,
        )
        ctl = WeightBusController(
            gw.gateway, pub, fraction=0.5, healthy_window_s=0.4,
            min_requests=5,
        )
        try:
            v1 = pub.publish(linear_tree(1, 4))
            _wait(lambda: set(
                gw.gateway.fleet_versions().values()) == {v1},
                msg="fleet at v1")
            assert ctl.tick() is None
            assert gw.gateway.stable_version == v1  # bootstrap
            v2 = pub.publish(linear_tree(2, 4))
            _wait(lambda: set(
                gw.gateway.fleet_versions().values()) == {v2},
                msg="fleet at v2")
            assert ctl.tick() == "canary"
            assert gw.gateway.canary_version == v2
            # real traffic: episodes through the gateway accumulate
            # v2's request/latency stats
            promoted = False
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                _episode(gw.address)
                if ctl.tick() == "promote":
                    promoted = True
                    break
            assert promoted, gw.gateway.version_stats()
            assert gw.gateway.stable_version == v2
            assert gw.gateway.canary_version is None
            snap = _weight_counts(counters)
            assert snap["weight_canary_starts"] >= 1
            assert snap["weight_canary_promotions"] == 1
            assert snap.get("weight_canary_rollbacks", 0) == 0
            assert snap.get("weight_canary_routes", 0) >= 1
        finally:
            gw.close()
            for s in servers:
                s.close()


def test_controller_rolls_back_on_p99_regression_and_republishes():
    """Metric-driven rollback: the canary version's replica is slow
    (sleep-based per-row work), its REAL scraped p99 regresses past the
    threshold, the controller rolls the canary back, fresh episodes
    avoid the rejected version, and the stable weights are republished
    under a fresh version id."""
    from blendjax.serve import LinearModel, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread
    from blendjax.weights.controller import WeightBusController

    counters = EventCounters()
    # two buses: r0 rides pub_a (the stable weights), r1 rides pub_b
    # (the "bad" rollout: same tree recipe, but its replica is slow) —
    # a persistently mixed-version fleet, which is exactly the canary
    # window's subject
    with WeightPublisher(counters=counters,
                         version_base=0).start() as pub_a, \
            WeightPublisher(version_base=10,
                            counters=counters).start() as pub_b:
        s0 = start_server_thread(
            LinearModel(obs_dim=4, slots=8, seed=0),
            counters=EventCounters(),
            subscriber=WeightSubscriber(pub_a.address,
                                        counters=counters),
        )
        s1 = start_server_thread(
            LinearModel(obs_dim=4, slots=8, seed=0, work_us=20000),
            counters=EventCounters(),
            subscriber=WeightSubscriber(pub_b.address,
                                        counters=counters),
        )
        gw = start_gateway_thread(
            [s0.address, s1.address], counters=counters,
            scrape_interval_s=0.1,
        )
        ctl = WeightBusController(
            gw.gateway, pub_a, fraction=0.5, healthy_window_s=30.0,
            min_requests=5, max_p99_x=3.0,
        )
        try:
            va = pub_a.publish(linear_tree(1, 4))     # v1 on r0
            vb = pub_b.publish(linear_tree(11, 4))    # v11 on r1
            _wait(lambda: sorted(
                v for v in gw.gateway.fleet_versions().values()
                if v is not None) == [va, vb], msg="mixed fleet")
            gw.gateway.set_stable(va)
            assert ctl.tick() == "canary"
            assert gw.gateway.canary_version == vb
            rolled = False
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                _episode(gw.address, timeoutms=8000)
                if ctl.tick() == "rollback":
                    rolled = True
                    break
            assert rolled, gw.gateway.version_stats()
            assert gw.gateway.rejected_version == vb
            snap = _weight_counts(counters)
            assert snap["weight_canary_rollbacks"] == 1
            # the stable weights were republished under a fresh id and
            # became the new stable reference
            assert snap["weight_rollback_publishes"] == 1
            assert gw.gateway.stable_version == pub_a.version > va
            # fresh episodes now avoid the rejected version's replica
            for _ in range(4):
                rep, vs = _episode(gw.address)
                assert rep == "r0", (rep, vs)
                assert vb not in vs
        finally:
            gw.close()
            s0.close()
            s1.close()


class _GatewayStub:
    """The controller-facing slice of ServeGateway, deterministic: the
    test writes fleet versions and per-version stats directly instead
    of standing up replicas (the live-traffic arms above already lock
    the real gateway's side of the contract)."""

    def __init__(self):
        self.stable_version = None
        self.canary_version = None
        self.rejected_version = None
        self.versions = {}
        self.stats = {}

    def fleet_versions(self):
        return dict(self.versions)

    def version_stats(self):
        return {v: dict(r) for v, r in self.stats.items()}

    def set_stable(self, version):
        self.stable_version = version

    def canary(self, version, fraction):
        self.canary_version = version

    def promote(self):
        self.stable_version = self.canary_version
        self.canary_version = None

    def rollback(self):
        self.rejected_version = self.canary_version
        self.canary_version = None


def test_controller_verdict_timeout_rolls_back_wedged_canary():
    """Liveness bound on the canary window: a canary that never
    replies (wedged or crash-looping replica) can never reach
    ``min_requests``, so no error-rate/p99 verdict would ever fire —
    after ``verdict_timeout_s``, IF the fleet served enough traffic
    that the canary's fraction share should have met ``min_requests``,
    the canary is rolled back as unreachable.  An idle fleet gives no
    verdict and the window stays open."""
    from blendjax.weights.controller import WeightBusController

    gw = _GatewayStub()
    ctl = WeightBusController(gw, None, fraction=0.5, min_requests=10,
                              healthy_window_s=60.0,
                              verdict_timeout_s=0.05)
    gw.versions = {"r0": 1, "r1": 1}
    gw.stats = {1: {"requests": 0, "errors": 0}}
    assert ctl.tick() is None and gw.stable_version == 1  # bootstrap
    gw.versions = {"r0": 2, "r1": 1}
    assert ctl.tick() == "canary" and gw.canary_version == 2
    # idle fleet: the deadline alone must NOT roll back — nothing to
    # judge a healthy-but-unexercised canary against
    time.sleep(0.06)
    assert ctl.tick() is None
    assert gw.canary_version == 2
    # stable serves 100 requests, the canary's 50% share should have
    # been ~50 >> min_requests, yet it produced zero replies: wedged
    gw.stats[1]["requests"] = 100
    time.sleep(0.06)
    assert ctl.tick() == "rollback"
    assert gw.rejected_version == 2
    assert gw.canary_version is None


# ---------------------------------------------------------------------------
# the flywheel (acceptance): learner -> bus -> serve fleet -> clients
# ---------------------------------------------------------------------------


def test_flywheel_learner_publishes_fleet_swaps_clients_observe():
    """End to end: a real learner trains off-policy, publishes every
    K updates, two subscribed policy servers behind a gateway hot-swap
    between ticks, live clients observe ``weight_version`` advance
    monotonically with ZERO errors and zero dropped leases, and the
    controller promotes a canary on the way."""
    import jax

    from blendjax.models.actor_learner import ActorLearner
    from blendjax.models import policy
    from blendjax.replay import ReplayBuffer
    from blendjax.serve import PolicyModel, ServeClient, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread
    from blendjax.weights.controller import WeightBusController

    rng = np.random.default_rng(0)
    buf = ReplayBuffer(256, seed=0)
    for _ in range(128):
        buf.append({
            "obs": rng.standard_normal(4).astype(np.float32),
            "action": np.int32(rng.integers(0, 3)),
            "reward": np.float32(rng.standard_normal()),
            "next_obs": rng.standard_normal(4).astype(np.float32),
            "done": np.bool_(False),
        })
    counters = EventCounters()
    pub = WeightPublisher(counters=counters).start()
    learner = ActorLearner(
        None, 4, 3, replay=buf, weight_bus=pub, publish_every=2,
        seed=0,
    )
    init_params = jax.device_get(
        policy.init(jax.random.PRNGKey(1), 4, 3)
    )
    servers = [
        start_server_thread(
            PolicyModel(policy.init(jax.random.PRNGKey(1), 4, 3), 4),
            counters=counters,
            subscriber=WeightSubscriber(pub.address, counters=counters),
        )
        for _ in range(2)
    ]
    del init_params
    gw = start_gateway_thread(
        [s.address for s in servers], counters=counters,
        scrape_interval_s=0.1,
    )
    # promote is this test's subject: loosen the regression thresholds
    # so CI noise cannot divert a healthy canary into the rollback
    # path (which has its own dedicated test)
    ctl = WeightBusController(gw.gateway, pub, fraction=0.5,
                              healthy_window_s=0.3, min_requests=5,
                              max_p99_x=100.0, max_error_rate=1.0)
    stop = threading.Event()
    observed = [[] for _ in range(2)]   # per-client version sequences
    errors = []

    def client_loop(i):
        c = ServeClient(gw.address, timeoutms=8000)
        obs = np.zeros(4, np.float32)
        try:
            c.reset()
            while not stop.is_set():
                r = c.step(obs)
                v = r.get("weight_version")
                if v is not None and (not observed[i]
                                      or observed[i][-1] != v):
                    observed[i].append(v)
            c.close_episode()
        except Exception as exc:  # noqa: BLE001 - the assertion subject
            errors.append(f"client {i}: {type(exc).__name__}: {exc}")
        finally:
            c.close()

    threads = [threading.Thread(target=client_loop, args=(i,),
                                daemon=True) for i in range(2)]
    try:
        for t in threads:
            t.start()
        # the controller runs THROUGH training (the real deployment
        # shape): it bootstraps stable at the first version and opens
        # canary windows as later publishes land
        ctl.start(interval_s=0.05)
        stats = learner.run_offline(num_updates=8, batch_size=32)
        assert stats["updates"] == 8
        assert pub.version >= 4  # 8 updates / publish_every=2
        # training's publishes can land faster than the scrape/tick
        # cadence (the controller may first SEE the fleet already at
        # the final version and bootstrap it as stable) — so once the
        # fleet settles, roll out ONE more deliberate version: it is
        # strictly above whatever became stable, so a canary window
        # must open and promote
        _wait(lambda: gw.gateway.stable_version is not None,
              msg="stable bootstrap")
        v_final = pub.publish(jax.device_get(learner.state.params),
                              step=99)
        _wait(lambda: counters.get("weight_canary_promotions") >= 1
              and gw.gateway.stable_version == v_final
              and all(obs_i and obs_i[-1] == v_final
                      for obs_i in observed),
              timeout=20, msg="final promote + fleet-wide observation")
    finally:
        ctl.stop()
        stop.set()
        for t in threads:
            t.join(timeout=10)
        gw.close()
        for s in servers:
            s.close()
        pub.close()
    # the flywheel turned: clients observed the version advance,
    # strictly monotonically, with zero errors of any kind (no dropped
    # leases, no lost episodes, no refused steps)
    assert errors == []
    for seq in observed:
        assert seq, "client never observed a published version"
        assert seq == sorted(seq), seq
        assert seq[-1] == pub.version
    snap = _weight_counts(counters)
    assert snap["weight_published"] >= 4
    assert snap["weight_adopted"] >= 2  # both replicas swapped
    assert snap["weight_canary_promotions"] >= 1
    assert gw.gateway.stable_version == pub.version
    # zero stale-lease redirects: no episode was dropped by a swap
    assert counters.get("gateway_stale_lease_redirects") == 0


# ---------------------------------------------------------------------------
# chaos: publisher SIGKILL + replica catch-up gating
# ---------------------------------------------------------------------------


def _spawn_publisher(address, *extra):
    from blendjax.btt.launcher import child_env

    cmd = [
        sys.executable, "-m", "blendjax.weights.bus",
        "--address", address, "--obs-dim", "4",
    ] + list(extra)
    return subprocess.Popen(cmd, env=child_env(),
                            start_new_session=True)


@pytest.mark.chaos
def test_publisher_sigkill_mid_snapshot_is_invisible_to_clients():
    """THE publisher crash contract: SIGKILL the publisher process
    parked mid-snapshot — the server keeps serving the last good
    version with ZERO client-visible errors, the torn-snapshot counter
    pins, and the respawned publisher's next (higher-version) snapshot
    is adopted."""
    from blendjax.replay.shard_client import free_port
    from blendjax.serve import LinearModel, ServeClient, start_server_thread

    counters = EventCounters()
    addr = f"tcp://127.0.0.1:{free_port()}"
    obs = np.arange(4, dtype=np.float32)
    # the publisher waits for the server's subscription, streams v1
    # whole, then parks v2 after 1 chunk (64-byte w in 16-byte
    # chunks) — the kill deterministically lands MID-snapshot
    pub_proc = _spawn_publisher(
        addr, "--interval-ms", "100", "--publishes", "2",
        "--version-base", "0", "--chunk-bytes", "16",
        "--hold-at-version", "2", "--hold-after-chunks", "1",
        "--wait-subscribers", "1",
    )
    h = None
    pub2 = None
    errors = []
    try:
        h = start_server_thread(
            LinearModel(obs_dim=4, slots=4, seed=0), counters=counters,
            subscriber=WeightSubscriber(addr, counters=counters,
                                        stall_timeout_s=1.0),
        )
        c = ServeClient(h.address)
        c.reset()

        def step():
            try:
                return c.step(obs)
            except Exception as exc:  # noqa: BLE001 - the subject
                errors.append(exc)
                raise

        _wait(lambda: step().get("weight_version") == 1,
              msg="v1 adoption")
        w1 = linear_tree(1, 4)["w"]
        # v2 is parked mid-stream: the stall timeout tears it while the
        # server keeps serving v1
        _wait(lambda: _weight_counts(counters).get(
            "weight_torn_discarded", 0) >= 1, timeout=15,
            msg="torn counter")
        r = step()
        assert r["weight_version"] == 1
        np.testing.assert_allclose(
            r["pred"], obs @ w1 + np.float32(r["pos"]), rtol=1e-5
        )
        pub_proc.kill()
        pub_proc.wait(timeout=10)
        # through the outage: last good version, zero errors
        for _ in range(10):
            assert step()["weight_version"] == 1
        # respawn with a HIGHER version base: the next snapshot adopts
        pub2 = _spawn_publisher(
            addr, "--interval-ms", "200", "--version-base", "100",
        )
        _wait(lambda: (step().get("weight_version") or 0) > 100,
              timeout=20, msg="respawned publisher's snapshot adopted")
        r = step()
        v = r["weight_version"]
        np.testing.assert_allclose(
            r["pred"],
            obs @ linear_tree(v, 4)["w"] + np.float32(r["pos"]),
            rtol=1e-5,
        )
        assert errors == []  # learner/publisher death: client-invisible
        c.close()
    finally:
        for p in (pub_proc, pub2):
            if p is not None:
                try:
                    p.kill()
                except Exception:  # noqa: BLE001
                    pass
        if h is not None:
            h.close()


@pytest.mark.chaos
def test_respawned_replica_catches_up_before_canary_readmission():
    """Kill one subscribed replica of two: the watchdog respawns it,
    the gateway re-admits it for LIVENESS — but while a canary window
    is open, its fresh-episode traffic stays off the respawned replica
    until a scrape shows it caught up to the fleet's current version
    (the bus was deliberately silenced to hold it behind)."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.watchdog import FleetWatchdog
    from blendjax.serve import ServerFleet
    from blendjax.serve.gateway import start_gateway_thread

    counters = EventCounters()
    pub = WeightPublisher(counters=counters).start()
    with ServerFleet(2, model="linear", obs_dim=4, slots=8,
                     subscribe=pub.address) as fleet:
        gw = start_gateway_thread(
            fleet.addresses, counters=counters, scrape_interval_s=0.15
        )
        wd = FleetWatchdog(
            fleet, interval=0.2, restart=True,
            on_death=gw.gateway.notify_replica_death,
            on_respawn=gw.gateway.notify_replica_respawn,
        )
        try:
            with wd:
                v1 = pub.publish(linear_tree(1, 4))
                _wait(lambda: set(
                    gw.gateway.fleet_versions().values()) == {v1},
                    timeout=20, msg="fleet at v1")
                gw.gateway.set_stable(v1)
                v2 = pub.publish(linear_tree(2, 4))
                _wait(lambda: set(
                    gw.gateway.fleet_versions().values()) == {v2},
                    timeout=20, msg="fleet at v2")
                gw.gateway.canary(v2, fraction=0.5)
                # silence the bus, then kill r1: its respawn cannot
                # catch up until the bus answers again
                pub.stop()
                kill_instance(fleet, 1)
                _wait(lambda: counters.get(
                    "gateway_replica_respawns") >= 1, timeout=30,
                    msg="respawn re-admission")
                # re-admitted for liveness, NOT for canary traffic:
                # the respawned replica reports no version, so every
                # fresh episode lands on the caught-up replica
                _wait(lambda: gw.gateway.fleet_versions().get("r1",
                      "missing") is None, timeout=10,
                      msg="respawned replica reports no version")
                for _ in range(6):
                    rep, vs = _episode(gw.address)
                    assert rep == "r0", (rep, vs)
                    assert set(vs) == {v2}
                # un-silence the bus: r1 syncs to the CURRENT version
                # and only then rejoins the canary traffic split
                pub.start()
                _wait(lambda: gw.gateway.fleet_versions().get(
                    "r1") == v2, timeout=20, msg="r1 caught up")
                reps = set()
                deadline = time.monotonic() + 20
                while time.monotonic() < deadline and "r1" not in reps:
                    rep, vs = _episode(gw.address)
                    assert set(vs) == {v2}
                    reps.add(rep)
                assert "r1" in reps, "caught-up replica never re-joined"
        finally:
            gw.close()
            pub.close()


# ---------------------------------------------------------------------------
# bench schema + headline carry (satellites)
# ---------------------------------------------------------------------------


def test_weight_bench_emits_locked_schema():
    from benchmarks._common import WEIGHT_BENCH_KEYS
    from benchmarks.weight_benchmark import measure

    rec = measure(seconds=2.0, clients=3, publishes=2, snapshot_kb=16)
    assert all(k in rec for k in WEIGHT_BENCH_KEYS), [
        k for k in WEIGHT_BENCH_KEYS if k not in rec
    ]
    assert rec["swaps_observed"] == 2
    assert rec["weight_swap_ms"] is not None
    assert rec["weight_swap_ms"] >= rec["weight_swap_ms_p50"]
    assert rec["weight_swap_qps_dip_x"] is not None
    assert rec["weight_counters"].get("weight_adopted", 0) >= 2
    for stage in WEIGHT_STAGES:
        assert stage in rec["stages"], stage


def test_bench_headline_carries_weight_metrics():
    import bench

    wb = {
        "phase": "weight_bench", "clients": 6, "publishes": 8,
        "window_s": 10.0, "snapshot_kb": 256,
        "weight_swap_ms": 6.1, "weight_swap_ms_p50": 3.6,
        "weight_swap_qps_dip_x": 0.97, "qps_steady": 7300.0,
        "swaps_observed": 8, "swap_ms_all": [], "publish_ms_p50": 2.9,
        "weight_counters": {}, "stages": {},
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}},
                         weight_bench=wb)
    assert out["weight_bench"]["weight_swap_ms"] == 6.1
    line = bench.headline(out)
    assert line["weight_swap_ms"] == 6.1
    assert line["weight_swap_qps_dip_x"] == 0.97
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET


def test_bench_compare_guards_weight_metrics(tmp_path):
    """The trajectory guardrail knows the new metrics: weight_swap_ms
    is a CEILING (an increase is the regression), the QPS dip a floor —
    extracted from the full-artifact nesting like every other phase."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_compare_w",
        os.path.join(repo, "scripts", "bench_compare.py"),
    )
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)

    def metrics(swap_ms, dip):
        p = tmp_path / f"a{swap_ms}.json"
        p.write_text(json.dumps({
            "metric": "m", "value": 1.0,
            "weight_bench": {"weight_swap_ms": swap_ms,
                             "weight_swap_qps_dip_x": dip},
        }))
        return bc.extract_metrics(str(p))

    old = metrics(6.0, 1.0)
    assert old["weight_swap_ms"] == 6.0
    rows, regressions = bc.compare(old, metrics(7.0, 0.95),
                                   bc.DEFAULT_FLOORS)
    bad = {r["metric"] for r in rows if not r["ok"]}
    assert "weight_swap_ms" not in bad  # 7/6 under the 1.5 ceiling
    assert "weight_swap_qps_dip_x" not in bad
    rows, regressions = bc.compare(old, metrics(12.0, 0.5),
                                   bc.DEFAULT_FLOORS)
    bad = {r["metric"] for r in rows if not r["ok"]}
    assert {"weight_swap_ms", "weight_swap_qps_dip_x"} <= bad
    assert regressions >= 2
    swap_row = next(r for r in rows
                    if r["metric"] == "weight_swap_ms")
    assert swap_row["direction"] == "down"  # lower-is-better declared
