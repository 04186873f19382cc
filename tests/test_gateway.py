"""ServeGateway tests (docs/serving.md "ServeGateway").

The load-bearing ones: episode-lease affinity (every step of an episode
lands on the replica that owns its KV-cache row, witnessed by
per-replica seeds), the drain lifecycle, multi-model routing, and the
kill-one-of-three chaos scenario — a SIGKILLed replica respawned by
``FleetWatchdog`` costs its episodes exactly one actionable stale-lease
error before they resume via ``reset()``, with every ACKED request
applied exactly once through the extra hop (the position-sensitive
``LinearModel`` makes a double- or un-applied step visible in every
later prediction).
"""

import time

import numpy as np
import pytest

from blendjax.btt.faults import FaultPolicy
from blendjax.utils.timing import (
    GATEWAY_EVENTS,
    GATEWAY_STAGES,
    EventCounters,
    StageTimer,
)


def _gateway_counts(counters):
    return {k: v for k, v in counters.snapshot().items()
            if k.startswith("gateway_")}


def _two_replicas(seeds=(0, 7), slots=8, obs_dim=4):
    """Two in-thread linear servers with DIFFERENT seeds: predictions
    witness which replica served an episode."""
    from blendjax.serve import LinearModel, start_server_thread

    handles = [
        start_server_thread(
            LinearModel(obs_dim=obs_dim, slots=slots, seed=s),
            counters=EventCounters(),
        )
        for s in seeds
    ]
    return handles


def _ref_w(seed, obs_dim=4):
    from blendjax.serve import LinearModel

    return LinearModel(obs_dim=obs_dim, slots=1, seed=seed).w


# ---------------------------------------------------------------------------
# routing: affinity, spread, drain
# ---------------------------------------------------------------------------


def test_gateway_lease_affinity_and_replica_stamp():
    """Every step of one episode is served by ONE replica (its
    predictions stay consistent with a single weight matrix and a
    monotonically increasing position), the reply carries the serving
    replica's id, and the affinity counter pins the routing path."""
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas()
    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    ws = {"r0": _ref_w(0), "r1": _ref_w(7)}
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=counters,
            timer=StageTimer(), scrape_interval_s=0.1,
        ) as gw:
            clients = [ServeClient(gw.address, timeoutms=5000)
                       for _ in range(4)]
            for c in clients:
                c.reset()
            for k in range(3):
                for c in clients:
                    r = c.step(obs)
                    assert r["replica"] in ws
                    assert c.replica == r["replica"]
                    assert r["pos"] == k
                    np.testing.assert_allclose(
                        r["pred"],
                        obs @ ws[r["replica"]] + np.float32(k),
                    )
            snap = _gateway_counts(counters)
            assert snap["gateway_routed"] >= 16  # 4 resets + 12 steps
            assert snap["gateway_affinity_hits"] >= 12
            hello = clients[0].hello()
            assert hello["gateway"] is True
            assert set(hello["replicas"]) == {"r0", "r1"}
            # once a scrape lands, the gateway hello merges a healthy
            # replica's PR-10 capability fields, so hello consumers
            # written against a bare server work unchanged
            deadline = time.monotonic() + 5
            while "obs_dim" not in hello:
                assert time.monotonic() < deadline, hello
                time.sleep(0.02)
                hello = clients[0].hello()
            assert hello["obs_dim"] == 4
            assert hello["max_batch"] > 0
            for c in clients:
                c.close_episode()
                c.close()
    finally:
        for h in handles:
            h.close()


def test_gateway_spreads_fresh_episodes_across_replicas():
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas(seeds=(0, 0))
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=EventCounters(),
            scrape_interval_s=0.1,
        ) as gw:
            clients = [ServeClient(gw.address, timeoutms=5000)
                       for _ in range(6)]
            for c in clients:
                c.reset()
            # the optimistic pending-live estimate spreads a reset
            # burst even before any scrape lands
            per_replica = [
                h.server.counters.get("serve_resets") for h in handles
            ]
            assert all(n > 0 for n in per_replica), per_replica
            for c in clients:
                c.close_episode()
                c.close()
    finally:
        for h in handles:
            h.close()


def test_gateway_drain_lifecycle():
    """A draining replica receives no fresh episodes but finishes its
    live ones; undrain restores it; the RPC admin surface mirrors the
    method one."""
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas(seeds=(0, 0))
    counters = EventCounters()
    obs = np.zeros(4, np.float32)
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=counters,
            scrape_interval_s=0.1,
        ) as gw:
            live = ServeClient(gw.address, timeoutms=5000)
            live.reset()
            live.step(obs)
            victim = live.replica
            gw.gateway.drain(victim)
            vic_counters = handles[int(victim[1:])].server.counters
            resets_before = vic_counters.get("serve_resets")
            others = [ServeClient(gw.address, timeoutms=5000)
                      for _ in range(4)]
            for c in others:
                c.reset()
            assert vic_counters.get("serve_resets") == resets_before
            # the drained replica still serves its live episode
            assert live.step(obs)["replica"] == victim
            # undrain via the RPC admin surface; fresh episodes return
            admin = ServeClient(gw.address, timeoutms=5000)
            reply = admin.rpc("undrain", {"replica": victim})
            assert reply["draining"] == []
            assert _gateway_counts(counters)["gateway_drains"] == 1
            # draining every replica makes a fresh reset fail actionably
            for rid in ("r0", "r1"):
                admin.rpc("drain", {"replica": rid})
            denied = ServeClient(
                gw.address, timeoutms=5000,
                fault_policy=FaultPolicy(max_retries=0),
            )
            with pytest.raises(RuntimeError, match="no healthy replica"):
                denied.reset()
            for c in others + [live, admin, denied]:
                c.close()
    finally:
        for h in handles:
            h.close()


# ---------------------------------------------------------------------------
# multi-model routing through the gateway
# ---------------------------------------------------------------------------


def test_gateway_routes_by_model_id():
    """Replicas hosting different model ids: a client pinned to model
    "b" is served by the replica hosting it (seed witness), and an
    unhosted model id errors actionably."""
    from blendjax.serve import LinearModel, ServeClient, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread

    obs = np.arange(4, dtype=np.float32)
    ha = start_server_thread(
        {"a": LinearModel(obs_dim=4, slots=4, seed=0)},
        counters=EventCounters(),
    )
    hb = start_server_thread(
        {"b": LinearModel(obs_dim=4, slots=4, seed=7)},
        counters=EventCounters(),
    )
    try:
        with start_gateway_thread(
            [ha.address, hb.address], counters=EventCounters(),
            scrape_interval_s=0.05,
        ) as gw:
            # wait for the model map to be learned from the scrape
            deadline = time.monotonic() + 5
            cb = ServeClient(gw.address, model="b", timeoutms=5000)
            while time.monotonic() < deadline:
                hello = cb.hello()
                if set(hello["models"]) == {"a", "b"}:
                    break
                time.sleep(0.02)
            cb.reset()
            r = cb.step(obs)
            assert r["replica"] == "r1"
            np.testing.assert_allclose(r["pred"], obs @ _ref_w(7))
            bogus = ServeClient(
                gw.address, model="zzz", timeoutms=5000,
                fault_policy=FaultPolicy(max_retries=0),
            )
            with pytest.raises(RuntimeError, match="zzz"):
                bogus.reset()
            cb.close_episode()
            cb.close()
            bogus.close()
    finally:
        ha.close()
        hb.close()


# ---------------------------------------------------------------------------
# lease errors, prefill through the hop
# ---------------------------------------------------------------------------


def test_gateway_unknown_lease_errors_and_noop_close():
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas()
    counters = EventCounters()
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=counters,
        ) as gw:
            c = ServeClient(gw.address, timeoutms=5000,
                            fault_policy=FaultPolicy(max_retries=0))
            c.slot, c.episode = 0, 424242  # never admitted
            with pytest.raises(RuntimeError,
                               match="reset\\(\\) and resume"):
                c.step(np.zeros(4, np.float32))
            # a stale close is answered, never an error (the server's
            # own no-op close semantics through the hop)
            c.slot, c.episode = 0, 424242
            assert not c.close_episode()
            assert _gateway_counts(
                counters
            )["gateway_stale_lease_redirects"] >= 1
            c.close()
    finally:
        for h in handles:
            h.close()


def test_gateway_prefill_admission_end_to_end():
    """reset(prefix=...) rides the hop: the lease comes back rewritten,
    the prefill prediction matches T serial steps, and the episode
    continues at position T on the SAME replica."""
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas(seeds=(3, 3))
    w = _ref_w(3)
    rng = np.random.default_rng(5)
    prefix = rng.standard_normal((6, 4)).astype(np.float32)
    obs = rng.standard_normal(4).astype(np.float32)
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=EventCounters(),
        ) as gw:
            c = ServeClient(gw.address, timeoutms=5000)
            reply = c.reset(prefix=prefix)
            assert reply["pos"] == 6
            np.testing.assert_allclose(
                reply["pred"], prefix[-1] @ w + np.float32(5)
            )
            r = c.step(obs)
            assert r["pos"] == 6
            assert r["replica"] == reply["replica"]
            np.testing.assert_allclose(r["pred"], obs @ w + np.float32(6))
            c.close_episode()
            c.close()
    finally:
        for h in handles:
            h.close()


# ---------------------------------------------------------------------------
# telemetry plane + client diagnosability
# ---------------------------------------------------------------------------


def test_gateway_is_a_scrapeable_hub_remote():
    from blendjax.obs.hub import TelemetryHub
    from blendjax.serve import ServeClient
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas()
    counters, timer = EventCounters(), StageTimer()
    try:
        with start_gateway_thread(
            [h.address for h in handles], counters=counters, timer=timer,
        ) as gw:
            c = ServeClient(gw.address, timeoutms=5000)
            c.reset()
            for _ in range(3):
                c.step(np.zeros(4, np.float32))
            hub = TelemetryHub()
            c.register_with_hub(hub, "gateway")
            snap = hub.scrape()
            assert snap["counters"]["gateway_routed"] >= 4
            assert snap["counters"]["gateway_affinity_hits"] >= 3
            # zero-fill: every gateway counter AND stage is present
            for name in GATEWAY_EVENTS:
                assert name in snap["counters"], name
            for stage in GATEWAY_STAGES:
                assert stage in snap["stages"], stage
            assert snap["stages"]["gw_route"]["count"] >= 4
            assert snap["stages"]["gw_reply"]["p99_ms"] >= 0.0
            c.close()
    finally:
        for h in handles:
            h.close()


def test_client_surfaces_replica_id_in_error_and_spans():
    """The small-fix satellite: after serving through a gateway, the
    client knows which replica answered last — a transport failure's
    ServeRPCError text names it, and the client RPC spans carry it."""
    from blendjax.obs.spans import SpanRecorder
    from blendjax.serve import ServeClient, ServeRPCError
    from blendjax.serve.gateway import start_gateway_thread

    handles = _two_replicas(seeds=(0, 0))
    rec = SpanRecorder()
    gw = start_gateway_thread(
        [h.address for h in handles], counters=EventCounters(),
    )
    try:
        c = ServeClient(
            gw.address, timeoutms=300, span_recorder=rec,
            fault_policy=FaultPolicy(max_retries=0, circuit_threshold=0),
        )
        c.reset()
        c.step(np.zeros(4, np.float32))
        assert c.replica in ("r0", "r1")
        served_by = c.replica
        spans = rec.drain()
        stamped = [s for s in spans
                   if (s.get("args") or {}).get("replica") == served_by]
        assert stamped, spans
        # kill the gateway: the next RPC times out and the error text
        # names the last replica that served this client
        gw.close()
        gw = None
        with pytest.raises(ServeRPCError, match=served_by):
            c.step(np.zeros(4, np.float32))
        c.close()
    finally:
        if gw is not None:
            gw.close()
        for h in handles:
            h.close()


# ---------------------------------------------------------------------------
# exactly-once through the extra hop (chaos)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("transport", ["tcp", "shm"])
def test_exactly_once_through_gateway_with_wire_faults(transport):
    """Wire faults between client and GATEWAY: dropped replies and
    duplicated requests across the two-hop path still yield exactly one
    applied step per submitted request — the gateway forwards BTMID
    verbatim, re-forwards in-flight retries to the SAME replica, and
    answers executed retries from its own reply cache.  Parametrized
    over both wires (ISSUE-12): ``tcp`` injects at the TCP chunk layer
    (ChaosProxy, shm pinned off), ``shm`` at the ring frame layer
    (ShmChaos) on the client->gateway hop — with the gateway->replica
    hop ALSO riding its own shm channel."""
    from blendjax.btt.chaos import ChaosProxy
    from blendjax.btt.shm_rpc import ShmChaos, enabled
    from blendjax.serve import LinearModel, ServeClient, start_server_thread
    from blendjax.serve.gateway import start_gateway_thread

    if transport == "shm" and not enabled():
        pytest.skip("shm rpc unavailable on this host")
    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    ref = LinearModel(obs_dim=4, slots=2, seed=0)
    ref.reset_rows(np.asarray([0]))
    h = start_server_thread(
        LinearModel(obs_dim=4, slots=2, seed=0), counters=EventCounters()
    )
    proxy = None
    chaos = None
    try:
        with start_gateway_thread(
            [h.address], counters=counters, scrape_interval_s=0.1
        ) as gw:
            if transport == "tcp":
                proxy = ChaosProxy(gw.address)
                client = ServeClient(
                    proxy.address,
                    fault_policy=FaultPolicy(
                        max_retries=4, backoff_base=0.02,
                        backoff_max=0.1, circuit_threshold=0, seed=1,
                    ),
                    counters=counters, timeoutms=400, shm=False,
                )
            else:
                chaos = ShmChaos(seed=1)
                client = ServeClient(
                    gw.address,
                    fault_policy=FaultPolicy(
                        max_retries=4, backoff_base=0.02,
                        backoff_max=0.1, circuit_threshold=0, seed=1,
                    ),
                    counters=counters, timeoutms=400, shm_chaos=chaos,
                )
            client.reset()
            preds = []
            for t in range(16):
                if t == 4:
                    if proxy is not None:
                        proxy.drop_next("down")  # lose a reply -> retry
                    else:
                        assert client.transport == "shm", \
                            "client->gateway upgrade never happened"
                        chaos.drop_next("down")
                if t == 9:
                    (proxy or chaos).dup_next("up")  # duplicate request
                preds.append(client.step(obs)["pred"])
            want = [ref.step_rows(np.asarray([0]), obs[None])[0]
                    for _ in range(16)]
            np.testing.assert_allclose(np.stack(preds),
                                       np.stack(want))
            snap = counters.snapshot()
            assert snap.get("retries", 0) >= 1
            # the retry was healed on the gateway/replica side, not
            # by accident: a cache hit or an in-flight re-forward
            assert (
                snap.get("gateway_cache_hits", 0)
                + snap.get("gateway_dup_inflight", 0)
            ) >= 1, snap
            if transport == "shm":
                # the gateway->replica hop negotiated its own channel
                # off the scrape cycle: the step traffic moved bytes
                # through the replica's shm transport
                deadline = time.monotonic() + 5
                while time.monotonic() < deadline:
                    if any(r.shm is not None
                           for r in gw.gateway._replicas.values()):
                        break
                    time.sleep(0.05)
                assert any(r.shm is not None
                           for r in gw.gateway._replicas.values()), \
                    "gateway->replica hop never upgraded"
            client.close()
    finally:
        if proxy is not None:
            proxy.close()
        h.close()


@pytest.mark.chaos
def test_kill_one_replica_of_three_respawn_exactly_once():
    """THE fleet chaos contract (ISSUE-11): SIGKILL 1 of 3 replica
    processes mid-traffic; ``FleetWatchdog(restart=True)`` respawns it;
    clients behind the gateway observe only timeouts and ONE actionable
    stale-lease/unknown-slot error each, then resume after ``reset()``
    — and every ACKED request was applied exactly once (each acked
    prediction equals ``obs @ W + k`` where k counts the acks since the
    episode's reset; a double- or un-applied step would shift every
    later position).  Fault + gateway counters pinned."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.btt.watchdog import FleetWatchdog
    from blendjax.serve import ServeClient, ServerFleet
    from blendjax.serve.gateway import start_gateway_thread

    gw_counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    w = _ref_w(0)
    with ServerFleet(3, model="linear", obs_dim=4, slots=8) as fleet:
        gw = start_gateway_thread(
            fleet.addresses, counters=gw_counters, scrape_interval_s=0.15
        )
        wd = FleetWatchdog(
            fleet, interval=0.2, restart=True,
            on_death=gw.gateway.notify_replica_death,
            on_respawn=gw.gateway.notify_replica_respawn,
        )
        try:
            with wd:
                clients = []
                for i in range(4):
                    c = ServeClient(
                        gw.address, timeoutms=400,
                        fault_policy=FaultPolicy(
                            max_retries=1, backoff_base=0.05,
                            backoff_max=0.2, circuit_threshold=0,
                            seed=i,
                        ),
                        counters=EventCounters(),
                    )
                    c.reset()
                    clients.append(c)
                acked = [0] * len(clients)

                def acked_step(i):
                    """One step; on ack, verify exactly-once and count."""
                    r = clients[i].step(obs)
                    np.testing.assert_allclose(
                        r["pred"], obs @ w + np.float32(acked[i])
                    )
                    acked[i] += 1

                for i in range(len(clients)):
                    acked_step(i)
                # kill the replica that owns clients[1]'s episode, so a
                # client deterministically crosses the stale-lease path
                victim = int(clients[1].replica[1:])
                kill_instance(fleet, victim)
                # drive traffic through the outage: timeouts retry the
                # step; the actionable lease error resets the episode
                stale_errors = 0
                for i, c in enumerate(clients):
                    deadline = time.monotonic() + 30
                    done = 0
                    while time.monotonic() < deadline and done < 3:
                        try:
                            acked_step(i)
                            done += 1
                        except TimeoutError:
                            continue
                        except RuntimeError as exc:
                            assert "reset() and resume" in str(exc), exc
                            stale_errors += 1
                            while time.monotonic() < deadline:
                                try:
                                    c.reset(timeout_ms=800)
                                    acked[i] = 0
                                    break
                                except (TimeoutError, RuntimeError):
                                    time.sleep(0.1)
                    assert done == 3, f"client {i} never recovered"
                # at least the victim's client crossed the stale path
                assert stale_errors >= 1
                # let the respawn scrape land, then pin the counters
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    snap = _gateway_counts(gw_counters)
                    if snap.get("gateway_replica_respawns", 0) >= 1:
                        break
                    time.sleep(0.1)
                assert snap.get("gateway_replica_quarantined", 0) >= 1
                assert snap.get("gateway_replica_respawns", 0) >= 1
                assert snap.get("gateway_stale_lease_redirects", 0) >= 1
                assert wd.deaths and wd.deaths[-1][2]  # restarted
                # all three replicas alive behind the gateway again
                assert wd.alive == 3
                for c in clients:
                    c.close()
        finally:
            gw.close()
    # no leaked /dev/shm objects (ISSUE-12): the SIGKILLed replica ran
    # no cleanup, but the respawn path swept its generation and fleet
    # teardown swept the rest — rings, bells, client-side halves
    from blendjax.btt.shm_rpc import leaked_objects

    for p in fleet._procs:
        if p.shm_base is not None:
            assert not leaked_objects(p.shm_base), leaked_objects(
                p.shm_base
            )


# ---------------------------------------------------------------------------
# sharded data plane chaos (docs/serving.md "The sharded gateway")
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_kill_one_gateway_worker_exactly_once():
    """THE sharded-gateway chaos contract (ISSUE-16): SIGKILL 1 of 3
    gateway WORKER processes mid-traffic.  Clients direct-dialed onto
    the survivors keep stepping with ZERO errors — their traffic never
    touches the dead worker or the front.  The victim's clients observe
    only timeouts (the dead direct dial), then fall back to the front,
    which answers their stale partition with the ONE actionable
    stale-lease error (``reset() and resume``); after ``reset()`` they
    land on a live worker and every ACKED request was applied exactly
    once (the position witness: each acked prediction equals
    ``obs @ W + k`` with k the acks since that episode's reset — a
    double- or un-applied step shifts every later position).  The
    watchdog respawns the victim under its parent-pinned address and
    shm base; counters pin deaths, respawns and the stale-lease path;
    no ``/dev/shm`` leak survives the close."""
    from blendjax.btt.chaos import kill_instance
    from blendjax.serve import ServeClient, ServerFleet
    from blendjax.serve.gateway import start_sharded_gateway_thread

    gw_counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    w = _ref_w(0)
    with ServerFleet(2, model="linear", obs_dim=4, slots=16) as fleet:
        gw = start_sharded_gateway_thread(
            fleet.addresses, workers=3, counters=gw_counters,
            scrape_interval_s=0.15, watchdog_interval_s=0.2,
        )
        bases = list(gw.gateway._wbases)
        try:
            clients, acked = [], []

            def admit():
                c = ServeClient(
                    gw.address, timeoutms=600,
                    fault_policy=FaultPolicy(
                        max_retries=1, backoff_base=0.05,
                        backoff_max=0.2, circuit_threshold=0,
                        seed=len(clients),
                    ),
                    counters=EventCounters(),
                )
                c.reset()
                clients.append(c)
                acked.append(0)

            for _ in range(6):
                admit()
            # fresh traffic hashes by correlation id: with 6 episodes
            # the workers are almost surely not all the same, but the
            # test must not depend on hash luck — admit a few more
            # until the victim's partition AND a survivor both exist
            while (len({c.gw_worker for c in clients}) < 2
                   and len(clients) < 12):
                admit()
            tags = {c.gw_worker for c in clients}
            assert len(tags) >= 2, tags

            def acked_step(i):
                r = clients[i].step(obs)
                np.testing.assert_allclose(
                    r["pred"], obs @ w + np.float32(acked[i])
                )
                acked[i] += 1

            for i in range(len(clients)):
                acked_step(i)
                acked_step(i)
            victim_tag = clients[0].gw_worker
            survivors = [i for i, c in enumerate(clients)
                         if c.gw_worker != victim_tag]
            on_victim = [i for i, c in enumerate(clients)
                         if c.gw_worker == victim_tag]
            kill_instance(gw.gateway, int(victim_tag[2:]))
            # drive traffic through the outage: survivors must not see
            # a single error; the victim's clients ride timeouts ->
            # front fallback -> ONE stale-lease error -> reset -> resume
            stale_errors, survivor_errors = 0, 0
            for i in range(len(clients)):
                deadline = time.monotonic() + 30
                done = 0
                while time.monotonic() < deadline and done < 3:
                    try:
                        acked_step(i)
                        done += 1
                    except TimeoutError:
                        if i in survivors:
                            survivor_errors += 1
                        continue
                    except RuntimeError as exc:
                        assert "reset() and resume" in str(exc), exc
                        if i in survivors:
                            survivor_errors += 1
                        stale_errors += 1
                        while time.monotonic() < deadline:
                            try:
                                clients[i].reset(timeout_ms=800)
                                acked[i] = 0
                                break
                            except (TimeoutError, RuntimeError):
                                time.sleep(0.1)
                assert done == 3, f"client {i} never recovered"
            assert survivor_errors == 0
            assert stale_errors >= 1
            assert on_victim  # the stale path was actually exercised
            # the respawn rejoined under its pinned identity: wait for
            # its first answered scrape, then pin the counters
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                snap = _gateway_counts(gw_counters)
                if (snap.get("gateway_worker_respawns", 0) >= 1
                        and all(x.alive for x in gw.gateway._workers)):
                    break
                time.sleep(0.1)
            assert snap.get("gateway_worker_deaths", 0) >= 1, snap
            assert snap.get("gateway_worker_respawns", 0) >= 1, snap
            assert all(x.alive for x in gw.gateway._workers)
            # the actionable error came off the stale partition: the
            # front's dead-worker answer (gateway_lease_rehash) or the
            # respawned worker's unknown-lease answer — the merged
            # fleet view carries both, but a worker-side increment only
            # reaches it on the NEXT answered scrape, so wait one out
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                merged = gw.gateway.gateway_counters()
                if merged.get("gateway_stale_lease_redirects", 0) >= 1:
                    break
                time.sleep(0.1)
            assert merged.get("gateway_stale_lease_redirects", 0) >= 1, \
                merged
            for c in clients:
                c.close()
        finally:
            gw.close()
    # PR-12 hygiene through the sharded plane: the SIGKILLed worker ran
    # no cleanup, but its parent-pinned base prefix was swept before
    # the respawn and again at close
    from blendjax.btt.shm_rpc import leaked_objects

    for base in bases:
        if base is not None:
            assert not leaked_objects(base), leaked_objects(base)


@pytest.mark.chaos
def test_exactly_once_through_sharded_front_with_wire_faults():
    """Wire faults between client and the SHARDED front (ChaosProxy:
    dropped replies, duplicated requests) still yield exactly one
    applied step per submitted request.  The client is pinned to the
    front (``follow_redirects=False``) so every message rides the
    relay path: the front re-forwards a same-mid retry to the SAME
    worker (route cache), and the worker's dedupe/reply cache answers
    executed retries — the front itself holds no reply cache."""
    from blendjax.btt.chaos import ChaosProxy
    from blendjax.serve import LinearModel, ServeClient, start_server_thread
    from blendjax.serve.gateway import start_sharded_gateway_thread

    counters = EventCounters()
    obs = np.arange(4, dtype=np.float32)
    ref = LinearModel(obs_dim=4, slots=2, seed=0)
    ref.reset_rows(np.asarray([0]))
    h = start_server_thread(
        LinearModel(obs_dim=4, slots=2, seed=0), counters=EventCounters()
    )
    proxy = None
    try:
        with start_sharded_gateway_thread(
            [h.address], workers=2, counters=counters,
            scrape_interval_s=0.1, supervise=False,
        ) as gw:
            proxy = ChaosProxy(gw.address)
            client = ServeClient(
                proxy.address,
                fault_policy=FaultPolicy(
                    max_retries=4, backoff_base=0.02,
                    backoff_max=0.1, circuit_threshold=0, seed=1,
                ),
                counters=counters, timeoutms=600, shm=False,
                follow_redirects=False,
            )
            client.reset()
            preds = []
            for t in range(16):
                if t == 4:
                    proxy.drop_next("down")  # lose a reply -> retry
                if t == 9:
                    proxy.dup_next("up")     # duplicate a request
                preds.append(client.step(obs)["pred"])
            want = [ref.step_rows(np.asarray([0]), obs[None])[0]
                    for _ in range(16)]
            np.testing.assert_allclose(np.stack(preds), np.stack(want))
            snap = counters.snapshot()
            assert snap.get("retries", 0) >= 1
            assert snap.get("gateway_front_relays", 0) >= 16
            # the retry was healed on the worker side, not by accident:
            # its dedupe or reply cache fired.  Worker counters reach
            # the front on the scrape cycle — wait one out
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                merged = gw.gateway.gateway_counters()
                if (merged.get("gateway_cache_hits", 0)
                        + merged.get("gateway_dup_inflight", 0)) >= 1:
                    break
                time.sleep(0.05)
            assert (
                merged.get("gateway_cache_hits", 0)
                + merged.get("gateway_dup_inflight", 0)
            ) >= 1, merged
            client.close()
    finally:
        if proxy is not None:
            proxy.close()
        h.close()


# ---------------------------------------------------------------------------
# bench schema + headline carry (satellites)
# ---------------------------------------------------------------------------


def test_gateway_bench_emits_locked_schema():
    from benchmarks._common import GATEWAY_BENCH_KEYS
    from benchmarks.serve_benchmark import measure_gateway

    rec = measure_gateway(seconds=1.6, clients=4, replicas=2,
                          work_us=100, rounds=1)
    assert all(k in rec for k in GATEWAY_BENCH_KEYS), [
        k for k in GATEWAY_BENCH_KEYS if k not in rec
    ]
    assert rec["gateway_qps"] > 0
    assert rec["gateway_qps_1replica"] > 0
    assert rec["gateway_scale_x"] is not None
    assert rec["gateway_p99_ms"] >= rec["gateway_p50_ms"]
    for stage in GATEWAY_STAGES:
        assert stage in rec["stages"], stage
    assert rec["gateway_counters"].get("gateway_drains", 0) >= 1
    # 1-worker mode: the shard-phase keys ride as None, never missing
    assert rec["gateway_workers"] == 1
    assert rec["gateway_qps_1worker"] is None
    assert rec["gateway_qps_nworker"] is None
    assert rec["gateway_shard_x"] is None
    assert rec["shard_profile"] is None


@pytest.mark.chaos
def test_sharded_gateway_bench_emits_shard_phase():
    """``--gateway-workers 2`` adds the shard phase: same locked
    schema, with the 1-worker/N-worker pair, its ratio and the
    shard-phase fleet profile populated (docs/serving.md)."""
    from benchmarks._common import GATEWAY_BENCH_KEYS
    from benchmarks.serve_benchmark import measure_gateway

    rec = measure_gateway(seconds=2.4, clients=4, replicas=2,
                          work_us=100, rounds=1, gateway_workers=2,
                          shard_work_us=50, shard_obs_dim=16,
                          shard_clients=4)
    assert all(k in rec for k in GATEWAY_BENCH_KEYS), [
        k for k in GATEWAY_BENCH_KEYS if k not in rec
    ]
    assert rec["gateway_workers"] == 2
    assert rec["gateway_qps"] > 0
    assert rec["gateway_qps_1worker"] > 0
    assert rec["gateway_qps_nworker"] > 0
    assert rec["gateway_shard_x"] is not None
    assert len(rec["shard_pair_ratios"]) == 1
    assert rec["shard_profile"] == {
        "work_us": 50, "obs_dim": 16, "clients": 4,
    }
    # the sharded plane's lifecycle showed up in the merged counters
    assert rec["gateway_counters"].get("gateway_front_relays", 0) >= 1


def test_bench_headline_carries_gateway_metrics():
    import json

    import bench

    gb = {
        "phase": "gateway_bench", "replicas": 3, "clients": 16,
        "work_us": 2000, "rounds": 3, "window_s": 2.5,
        "gateway_qps": 834.0, "gateway_qps_1replica": 372.0,
        "gateway_p50_ms": 18.0, "gateway_p99_ms": 47.1,
        "gateway_scale_x": 2.24, "pair_ratios": [2.2, 2.3],
        "gateway_workers": 2, "gateway_qps_1worker": 610.0,
        "gateway_qps_nworker": 845.0, "gateway_shard_x": 1.39,
        "shard_pair_ratios": [1.3, 1.4],
        "shard_profile": {"work_us": 500, "obs_dim": 128,
                          "clients": 12},
        "gateway_counters": {}, "stages": {},
    }
    sb = {
        "phase": "serve_bench", "model": "seqformer", "clients": 8,
        "serve_qps": 2650.0, "serve_p50_ms": 2.4, "serve_p99_ms": 6.4,
        "serve_int8_x": 0.98,
        "serve_prefill_x": 14.9,
        "serve_qps_modes": {}, "stages": {},
    }
    out = bench.assemble({"host_stream": {"items_per_sec": 1.0}},
                         serve_bench=sb, gateway_bench=gb)
    assert out["gateway_bench"]["gateway_scale_x"] == 2.24
    assert out["gateway_bench"]["gateway_shard_x"] == 1.39
    assert out["serve_bench"]["serve_prefill_x"] == 14.9
    line = bench.headline(out)
    assert line["gateway_qps"] == 834.0
    assert line["gateway_shard_x"] == 1.39
    assert len(json.dumps(line).encode()) <= bench.HEADLINE_BYTE_BUDGET
    assert line["gateway_p99_ms"] == 47.1
    assert line["gateway_scale_x"] == 2.24
    assert line["serve_prefill_x"] == 14.9
    assert len(json.dumps(line)) + 1 <= bench.HEADLINE_BYTE_BUDGET
